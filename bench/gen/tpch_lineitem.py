"""The TPC-H ``lineitem`` table, generated from a seed.

Columns, widths and value ranges follow the TPC-H specification's
schema (clause 1.4) and its generation rules for LINEITEM and the
ORDERS fields it derives from (clause 4.2.3):

- orders have 1-7 lines each; order keys are sparse, the first 8 of
  every 32 (so SF1's 1,500,000 orders reach key 6,000,000);
- ``l_partkey`` is uniform in [1, SF * 200,000]; ``l_suppkey`` is one of
  the part's four suppliers, ``(p + i * (S/4 + (p-1)/S)) mod S + 1``;
- ``l_quantity`` is uniform in [1, 50]; ``l_extendedprice`` is the
  quantity times the part's retail price,
  ``(90000 + (p/10 mod 20001) + 100 * (p mod 1000)) / 100``;
- ``l_discount`` is uniform in [0.00, 0.10], ``l_tax`` in [0.00, 0.08];
- the order date is uniform in [1992-01-01, 1998-12-31 - 151 days];
  ship = order + [1, 121], commit = order + [30, 90], receipt = ship +
  [1, 30] days;
- ``l_returnflag`` is R or A (even odds) when the receipt date is on or
  before 1995-06-17, else N; ``l_linestatus`` is O when the ship date is
  after it, else F;
- ``l_shipinstruct`` and ``l_shipmode`` are drawn from the spec's lists.

Encodings the spec leaves to the implementation (listed as ``assumed``
in the configuration): keys, line numbers, quantities and dates are
int32, dates as days since 1992-01-01; the three decimals are float64;
the flags and strings are fixed-width bytes at their spec widths; the
comment is 10-43 random letters and spaces rather than the spec's text
grammar.  The row count is fixed by the configuration: the last order
is cut short, or more orders drawn, until it is met exactly.
"""

from __future__ import annotations

import numpy as np

EPOCH = np.datetime64("1992-01-01")
ORDER_DATE_LAST = int((np.datetime64("1998-12-31") - 151 - EPOCH)
                      / np.timedelta64(1, "D"))
CURRENT_DATE = int((np.datetime64("1995-06-17") - EPOCH)
                   / np.timedelta64(1, "D"))
ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000
SUPPS_PER_SF = 10_000
SHIPINSTRUCT = (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                b"TAKE BACK RETURN")
SHIPMODE = (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB")
COMMENT_CHARS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)

# (name, dtype) in the spec's column order
SCHEMA = (
    ("l_orderkey", "int32"), ("l_partkey", "int32"), ("l_suppkey", "int32"),
    ("l_linenumber", "int32"), ("l_quantity", "int32"),
    ("l_extendedprice", "float64"), ("l_discount", "float64"),
    ("l_tax", "float64"), ("l_returnflag", "S1"), ("l_linestatus", "S1"),
    ("l_shipdate", "int32"), ("l_commitdate", "int32"),
    ("l_receiptdate", "int32"), ("l_shipinstruct", "S25"),
    ("l_shipmode", "S10"), ("l_comment", "S44"),
)


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    p = partkey.astype(np.int64)
    return 90000 + (p // 10) % 20001 + 100 * (p % 1000)


def generate(data: dict, seed: int) -> dict[str, np.ndarray]:
    """``data`` holds ``rows`` and ``scale_factor``; returns the table as
    a dict of equal-length columns in ``SCHEMA`` order."""
    n = int(data["rows"])
    sf = float(data["scale_factor"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x7C4]))

    # orders and their line counts, until exactly n lines
    n_orders = max(1, int(ORDERS_PER_SF * sf))
    counts = rng.integers(1, 8, n_orders)
    while counts.sum() < n:
        counts = np.concatenate([counts, rng.integers(1, 8, n_orders // 8
                                                      + 1)])
    ends = np.cumsum(counts)
    last = int(np.searchsorted(ends, n))          # order holding row n-1
    counts = counts[:last + 1].copy()
    counts[-1] -= int(ends[last] - n)
    order = np.repeat(np.arange(len(counts)), counts)
    first_row = np.concatenate([[0], np.cumsum(counts)[:-1]])
    linenumber = np.arange(n) - np.repeat(first_row, counts) + 1
    orderkey = (order // 8) * 32 + order % 8 + 1
    orderdate = rng.integers(0, ORDER_DATE_LAST + 1, len(counts))[order]

    parts = max(1, int(PARTS_PER_SF * sf))
    supps = max(4, int(SUPPS_PER_SF * sf))
    partkey = rng.integers(1, parts + 1, n)
    i = rng.integers(0, 4, n)
    suppkey = (partkey + i * (supps // 4 + (partkey - 1) // supps)) \
        % supps + 1
    quantity = rng.integers(1, 51, n)
    price = quantity * retail_price_cents(partkey) / 100.0
    discount = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0
    shipdate = orderdate + rng.integers(1, 122, n)
    commitdate = orderdate + rng.integers(30, 91, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    returned = rng.integers(0, 2, n).astype(bool)
    returnflag = np.where(receiptdate <= CURRENT_DATE,
                          np.where(returned, b"R", b"A"), b"N")
    linestatus = np.where(shipdate > CURRENT_DATE, b"O", b"F")
    shipinstruct = np.array(SHIPINSTRUCT, "S25")[
        rng.integers(0, len(SHIPINSTRUCT), n)]
    shipmode = np.array(SHIPMODE, "S10")[rng.integers(0, len(SHIPMODE), n)]
    comment = comments(rng, n)

    cols = {
        "l_orderkey": orderkey, "l_partkey": partkey, "l_suppkey": suppkey,
        "l_linenumber": linenumber, "l_quantity": quantity,
        "l_extendedprice": price, "l_discount": discount, "l_tax": tax,
        "l_returnflag": returnflag, "l_linestatus": linestatus,
        "l_shipdate": shipdate, "l_commitdate": commitdate,
        "l_receiptdate": receiptdate, "l_shipinstruct": shipinstruct,
        "l_shipmode": shipmode, "l_comment": comment,
    }
    return {name: np.ascontiguousarray(cols[name], dtype=dt)
            for name, dt in SCHEMA}


def comments(rng: np.random.Generator, n: int) -> np.ndarray:
    """n comments of 10-43 letters and spaces, as S44."""
    buf = np.zeros((n, 44), np.uint8)
    buf[:, :43] = COMMENT_CHARS[rng.integers(0, len(COMMENT_CHARS),
                                             (n, 43), dtype=np.uint8)]
    length = rng.integers(10, 44, n)
    buf[np.arange(44)[None, :] >= length[:, None]] = 0
    return buf.view("S44").ravel()
