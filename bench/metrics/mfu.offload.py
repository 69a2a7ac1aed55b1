"""The chain's share of the chip's bf16 peak: FLOPs of the passes in the
window over the window, against the peak."""


def read(rec):
    if rec["kind"] != "offload":
        return None
    return (rec["passes"] * rec["pass_flops"] / rec["window_s"]
            / rec["peaks"]["bf16_flops"] * 100.0)
