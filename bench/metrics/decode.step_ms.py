"""Mean blocked ``ServingEngine.decode_lanes`` time in the window (host
clock around the call, which ends with the tokens on the host)."""


def read(rec):
    if rec["kind"] != "chat" or not rec["decode_s"]:
        return None
    return sum(rec["decode_s"]) / len(rec["decode_s"]) * 1e3
