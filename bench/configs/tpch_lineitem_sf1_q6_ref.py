"""Plain reference for TPC-H Q6, the Forecasting Revenue Change Query
(spec 3.0.1 clause 2.4.6), over a generated lineitem table: numpy over
the table as generated, with nothing of the store, its codecs or its
scan engine in the way.

A request is its substitution parameters ``(year, discount, quantity)``
(clause 2.4.6.3), the discount in hundredths.  The rows that pass are
those shipped in ``[year-01-01, (year+1)-01-01)``, with a discount
between ``(discount - 1) / 100`` and ``(discount + 1) / 100`` inclusive
(the spec's exact decimals) and a quantity below ``quantity``.  The
answer is the sum of ``l_extendedprice * l_discount`` over them, the
products in float64 and their sum exactly rounded (``math.fsum``).

``float_dtype`` computes the products and their sum in another
precision: the configuration states float64, and float32 is the
control that has to fail the comparison.
"""

from __future__ import annotations

import datetime
import math

import numpy as np

EPOCH = datetime.date(1992, 1, 1)  # the generator's day 0


def day(year: int) -> int:
    """1 January of ``year``, in days since the generator's epoch."""
    return (datetime.date(year, 1, 1) - EPOCH).days


def passing(table: dict, req) -> np.ndarray:
    """The row mask of Q6's three predicates."""
    year, discount, quantity = req
    ship, disc = table["l_shipdate"], table["l_discount"]
    return ((ship >= day(year)) & (ship < day(year + 1))
            & (disc >= (discount - 1) / 100) & (disc <= (discount + 1) / 100)
            & (table["l_quantity"] < quantity))


def answer(table: dict, req, *, float_dtype=np.float64) -> float:
    mask = passing(table, req)
    prod = (table["l_extendedprice"][mask].astype(float_dtype)
            * table["l_discount"][mask].astype(float_dtype))
    if prod.dtype == np.float64:
        return math.fsum(prod.tolist())
    return float(prod.sum(dtype=prod.dtype))


# the limit of the number compared; PERF.md gives the readings it was
# set from (the program on a dozen seeds, the float32 control)
LIMITS = {"revenue_rel_gap": 1e-12}


def check(table: dict, answers) -> dict:
    """Worst relative gap over every kept answer, beside its limit.
    ``answers`` is ``[(req, got), ...]``."""
    worst = 0.0
    for req, got in answers:
        want = answer(table, req)
        worst = max(worst, abs(float(got) - want) / max(abs(want), 1e-300))
    return {"revenue_rel_gap": {"value": worst,
                                "limit": LIMITS["revenue_rel_gap"]}}
