"""Median time the front door took to compile a scan into a plan: the
program's ``front.compile`` span (``ScanEngine._compile`` and
``compile_hyperslab``), in ms."""

import statistics


def read(obs: dict):
    spans = obs["trace"]["spans"].get("front.compile")
    return statistics.median(spans) * 1e3 if spans else None
