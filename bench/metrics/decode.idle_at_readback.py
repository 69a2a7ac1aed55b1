"""Share of the traced window in which the device was idle while the
driving thread read the sampled tokens back to the host
(``dolma:decode.readback``, innermost span)."""
import program_spans


def read(rec):
    if rec["kind"] != "chat":
        return None
    return program_spans.idle_share(program_spans.load(), "decode.readback")
