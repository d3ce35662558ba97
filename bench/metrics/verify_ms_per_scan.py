"""OSD time checking the content digest of each served copy, in ms a
request over the window: the summed ``osd.verify`` spans
(``OSD._serve_item``) over the requests completed."""


def read(obs: dict):
    spans = obs["trace"]["spans"].get("osd.verify")
    n = obs["counters"].get("requests")
    return sum(spans) * 1e3 / n if spans and n else None
