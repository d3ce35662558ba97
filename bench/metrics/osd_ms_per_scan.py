"""OSD time serving each request, in ms a request over the window: the
summed ``osd.serve`` spans (``OSD.exec_cls_batch``, the whole call)
over the requests completed."""


def read(obs: dict):
    spans = obs["trace"]["spans"].get("osd.serve")
    n = obs["counters"].get("requests")
    return sum(spans) * 1e3 / n if spans and n else None
