"""Median time the trainer waited for its next batch: the
``loader.next`` span around ``next(loader)``, in ms."""

import statistics


def read(obs: dict):
    spans = obs["trace"]["spans"].get("loader.next")
    return statistics.median(spans) * 1e3 if spans else None
