"""Client time assembling each request's result, in ms a request over
the window: the summed ``front.assemble`` spans (frame decode,
``concat_tables``, ``combine_partials``, ``_assemble_array`` in
``ScanEngine.execute``) over the requests completed."""


def read(obs: dict):
    spans = obs["trace"]["spans"].get("front.assemble")
    n = obs["counters"].get("requests")
    return sum(spans) * 1e3 / n if spans and n else None
