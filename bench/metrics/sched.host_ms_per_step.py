"""Mean host time of one ``ContinuousScheduler.step`` outside its
``decode_lanes`` call: lane grants and resets, feeding, retiring, the
admission pass and KV offload."""


def read(rec):
    if rec["kind"] != "chat" or not rec["step_s"]:
        return None
    return (sum(rec["step_s"]) - sum(rec["decode_s"])) / len(rec["step_s"]) * 1e3
