"""``streaming_matmul``'s share of its roofline: for every stage of every
pass in the window, the larger of its FLOPs over peak and its least bytes
over HBM bandwidth, over the kernel's device time in the trace."""

#: how the compiled Pallas kernel's ops are named in the trace
KERNEL = "_matmul_call"


def read(rec):
    if rec["kind"] != "offload":
        return None
    dev_s = sum(s for name, s in rec["trace"]["ops"].items() if KERNEL in name)
    if dev_s <= 0:
        return None
    return rec["passes"] * rec["pass_roofline_s"] / dev_s * 100.0
