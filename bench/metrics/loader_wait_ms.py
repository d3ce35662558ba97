"""Median time the trainer waited inside the loader for its next
batch: the program's ``loader.wait`` span
(``ObjectDataLoader.__next__``), in ms."""

import statistics


def read(obs: dict):
    spans = obs["trace"]["spans"].get("loader.wait")
    return statistics.median(spans) * 1e3 if spans else None
