"""Host-to-HBM copy rate of the streamed stages: bytes over the seconds
``HostFetchEngine`` measured for its reads."""


def read(rec):
    if rec["kind"] != "offload" or rec["read_s"] <= 0:
        return None
    return rec["read_bytes"] / rec["read_s"] / 1e9
