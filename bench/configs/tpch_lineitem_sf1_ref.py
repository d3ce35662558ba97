"""Plain reference for scans over a generated table: the same request
answered by numpy over the table as generated, with nothing of the
store, its codecs or its scan engine in the way.

Semantics: a row range ``[a, b)`` first, then every filter ANDed, then
the projection (every column when there is none), then the aggregates
(``count``/``sum``/``min``/``max``/``mean`` of a column over the rows
that pass, sums exactly rounded).  Table-out answers keep the table's
row order.

``float_dtype`` computes the whole request in another precision: the
configuration states float64 columns, and float32 is the control that
has to fail the comparison.
"""

from __future__ import annotations

import math
import operator

import numpy as np

CMPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
        ">=": operator.ge, "==": operator.eq, "!=": operator.ne}


def answer(table: dict, traffic: dict, filters, rows, *,
           float_dtype=np.float64):
    if rows is not None:
        table = {k: v[rows[0]:rows[1]] for k, v in table.items()}
    cols = {k: (v.astype(float_dtype) if v.dtype.kind == "f" else v)
            for k, v in table.items()}
    n = len(next(iter(cols.values())))
    mask = np.ones(n, bool)
    for col, cmp, value in filters:
        mask &= CMPS[cmp](cols[col], cols[col].dtype.type(value))
    if traffic.get("aggregates"):
        out = {}
        for fn, col in traffic["aggregates"]:
            v = cols[col][mask]
            key = f"{fn}({col})"
            if fn == "count":
                out[key] = float(v.size)
            elif fn in ("sum", "mean"):
                # exact sum, but in the control's precision for its floats
                total = float(v.sum(dtype=v.dtype)) if v.dtype.kind == "f" \
                    and v.dtype != np.float64 else math.fsum(v.tolist())
                out[key] = total if fn == "sum" else total / max(v.size, 1)
            else:
                out[key] = float(getattr(v, fn)()) if v.size else \
                    {"min": math.inf, "max": -math.inf}[fn]
        return out
    keep = traffic.get("project") or list(cols)
    return {k: cols[k][mask] for k in keep}


def gaps(got, want) -> dict:
    """The numbers compared for one answer.  Tables: cells that differ
    (a row-count difference counts every cell of the longer table).
    Aggregates: exact gaps of count/min/max, relative gap of sums."""
    if isinstance(want, dict) and want and isinstance(
            next(iter(want.values())), np.ndarray):
        got = got or {}
        cells = 0
        if set(got) != set(want):
            return {"mismatched_cells": sum(len(v) for v in want.values())
                    + sum(len(np.asarray(v)) for v in got.values())}
        for k, w in want.items():
            g = np.asarray(got[k])
            if len(g) != len(w):
                cells += max(len(g), len(w))
            elif g.dtype != w.dtype:
                cells += len(w)
            else:
                cells += int(np.count_nonzero(g != w))
        return {"mismatched_cells": cells}
    out = {"exact_gap": 0.0, "sum_rel_gap": 0.0}
    for k, w in want.items():
        g = float(got[k])
        if k.startswith(("sum(", "mean(")):
            rel = abs(g - w) / max(abs(w), 1e-300)
            out["sum_rel_gap"] = max(out["sum_rel_gap"], rel)
        elif g != w:
            out["exact_gap"] = max(out["exact_gap"], abs(g - w))
    return out


# limits of the numbers compared; PERF.md gives the readings each was
# set from (program on a dozen seeds and more, the float32 control)
LIMITS = {"mismatched_cells": 0, "exact_gap": 0.0, "sum_rel_gap": 1e-12}


def check(table: dict, traffic: dict, filters, answers) -> dict:
    """Worst reading over every kept answer, each beside its limit.
    ``answers`` is ``[(rows, got), ...]``."""
    worst: dict[str, float] = {}
    for rows, got in answers:
        want = answer(table, traffic, filters, rows)
        for k, v in gaps(got, want).items():
            worst[k] = max(worst.get(k, 0), v)
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in worst.items()}
