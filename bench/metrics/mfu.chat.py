"""The whole step's share of the chip's bf16 peak: FLOPs of every token fed
in the window (prefill and decode, each at its lane's position) over the
window, against the peak."""


def read(rec):
    if rec["kind"] != "chat":
        return None
    return rec["token_flops"] / rec["window_s"] / rec["peaks"]["bf16_flops"] * 100.0
