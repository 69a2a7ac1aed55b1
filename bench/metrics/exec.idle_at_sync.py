"""Share of the traced window in which the device was idle while the
executor's driving thread waited for a stage's result after each stage
(``dolma:exec.sync``, innermost span)."""
import program_spans


def read(rec):
    if rec["kind"] != "offload":
        return None
    return program_spans.idle_share(program_spans.load(), "exec.sync")
