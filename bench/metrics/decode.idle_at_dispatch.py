"""Share of the traced window in which the device was idle while the
driving thread launched the decode step, from the token feed's copy to the
argmax enqueued (``dolma:decode.dispatch``, innermost span)."""
import program_spans


def read(rec):
    if rec["kind"] != "chat":
        return None
    return program_spans.idle_share(program_spans.load(), "decode.dispatch")
