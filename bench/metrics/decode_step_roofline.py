"""The decode step's share of its HBM roofline: the bytes the window's steps
need (every weight once, the K/V of positions up to each active lane's
position read, the new K/V written) over the device time of the decode-step
programs in the trace, against the chip's HBM bandwidth."""

#: the engine's jitted decode step, as its XLA module is named in the trace
MODULE_PREFIX = "jit__lambda"


def read(rec):
    if rec["kind"] != "chat":
        return None
    dev_s = sum(s for name, s in rec["trace"]["modules"].items()
                if name.startswith(MODULE_PREFIX))
    if dev_s <= 0:
        return None
    need_s = sum(rec["step_bytes"]) / rec["peaks"]["hbm_bytes_per_s"]
    return need_s / dev_s * 100.0
