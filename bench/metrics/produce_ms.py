"""Median time the loader's producer thread took to build one batch
from the store: the program's ``loader.produce`` span
(``ObjectDataLoader._producer``), in ms."""

import statistics


def read(obs: dict):
    spans = obs["trace"]["spans"].get("loader.produce")
    return statistics.median(spans) * 1e3 if spans else None
