"""Share of the fetch worker's host-to-HBM copy time (``dolma:fabric.read``
spans) during which the device was busy: how much of the dual buffer's
copying the kernels hide."""
import program_spans


def read(rec):
    if rec["kind"] != "offload":
        return None
    return program_spans.overlap_share(program_spans.load(), "fabric.read")
