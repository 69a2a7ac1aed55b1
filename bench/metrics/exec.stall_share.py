"""Share of the executor's pass time spent waiting at the access barrier
for a streamed stage (its ``stage_wait_us`` over ``elapsed_us``)."""


def read(rec):
    if rec["kind"] != "offload" or not rec["elapsed_us"]:
        return None
    return sum(rec["wait_us"]) / sum(rec["elapsed_us"]) * 100.0
