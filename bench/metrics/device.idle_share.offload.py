"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    if rec["kind"] != "offload":
        return None
    t = rec["trace"]
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
