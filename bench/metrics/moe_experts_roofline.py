"""The held-expert kernel's share of its roofline: the larger of the
routed work's FLOPs over peak and its least bytes (each active (layer,
expert) pair's weights once, each routed token's input and output) over
HBM bandwidth, over the kernel's device time in the trace. The counts are
the program's (``ServingEngine.moe_counts`` over the window). Each layer's
step is bound by its bytes at up to 64 tokens an expert, so the larger of
the two sums is the sum of the larger."""

#: how the compiled Pallas kernel's ops are named in the trace
KERNEL = "moe_held_experts"


def read(rec):
    if rec["kind"] != "chat" or "moe_bytes" not in rec:
        return None
    dev_s = sum(s for name, s in rec["trace"]["ops"].items() if KERNEL in name)
    if dev_s <= 0:
        return None
    p = rec["peaks"]
    need_s = max(rec["moe_flops"] / p["bf16_flops"],
                 rec["moe_bytes"] / p["hbm_bytes_per_s"])
    return need_s / dev_s * 100.0
