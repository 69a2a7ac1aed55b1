"""Share of the traced window in which the device was idle while the
executor's driving thread launched a stage's kernel
(``dolma:exec.dispatch``, innermost span)."""
import program_spans


def read(rec):
    if rec["kind"] != "offload":
        return None
    return program_spans.idle_share(program_spans.load(), "exec.dispatch")
