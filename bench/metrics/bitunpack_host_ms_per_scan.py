"""Host time of the bitunpack adapter, in ms a request over the window:
the summed ``codec.bitunpack`` spans (``kernels.bitunpack.
bitunpack_words``: pad, transfer, launch, fetch) over the requests
completed."""


def read(obs: dict):
    spans = obs["trace"]["spans"].get("codec.bitunpack")
    n = obs["counters"].get("requests")
    return sum(spans) * 1e3 / n if spans and n else None
