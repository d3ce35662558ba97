"""OSD time decoding the columns each pipeline needs, in ms a request
over the window: the summed ``osd.decode`` spans
(``OSD._decoded_table``, and ``select_packed``'s row copy) over the
requests completed."""


def read(obs: dict):
    spans = obs["trace"]["spans"].get("osd.decode")
    n = obs["counters"].get("requests")
    return sum(spans) * 1e3 / n if spans and n else None
