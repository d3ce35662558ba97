"""Median time of ``Scan.explain`` (the ``scan.plan`` span) in ms."""

import statistics


def read(obs: dict):
    spans = obs["trace"]["spans"].get("scan.plan")
    return statistics.median(spans) * 1e3 if spans else None
