"""Share of the traced window in which the device was idle while the
executor's driving thread waited at the access barrier for a streamed
stage's copy (``dolma:exec.barrier``, innermost span)."""
import program_spans


def read(rec):
    if rec["kind"] != "offload":
        return None
    return program_spans.idle_share(program_spans.load(), "exec.barrier")
