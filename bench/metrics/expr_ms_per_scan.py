"""OSD time evaluating aggregates' value expressions and their
partials, in ms a request over the window: the summed ``osd.expr``
spans (``objclass.apply_pipeline``) over the requests completed."""


def read(obs: dict):
    spans = obs["trace"]["spans"].get("osd.expr")
    n = obs["counters"].get("requests")
    return sum(spans) * 1e3 / n if spans and n else None
