"""The lineitem generator against the TPC-H rules, the scan reference
and its float32 control, and the three scan cells end to end at a tiny
size on the CPU (the harness's look for a chip skipped), with faults
planted in the timed path."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import control, load_module, run  # noqa: E402
from bench.drivers import scan  # noqa: E402

GEN = scan.load_generator("tpch_lineitem")
REF = load_module(ROOT / "bench/configs/tpch_lineitem_sf1_ref.py",
                  "ref_lineitem")
TINY = {"data": {"rows": 12_000, "unit_rows": 256, "scale_factor": 0.002},
        "store": {"object_bytes": 200_000}}
SEED = 2 ** 31 + 11  # past what 32 signed bits hold


@pytest.fixture(scope="module")
def table():
    return GEN.generate({"rows": 30_000, "scale_factor": 0.005}, SEED)


def test_lineitem_columns_widths_and_ranges(table):
    assert [(k, v.dtype.str) for k, v in table.items()] == [
        (k, np.dtype(d).str) for k, d in GEN.SCHEMA]
    assert all(len(v) == 30_000 for v in table.values())
    assert table["l_partkey"].min() >= 1
    assert table["l_partkey"].max() <= 0.005 * 200_000
    assert set(np.unique(table["l_linenumber"])) <= set(range(1, 8))
    assert table["l_linenumber"][0] == 1
    assert table["l_quantity"].min() >= 1 and table["l_quantity"].max() <= 50
    assert set(np.unique(np.round(table["l_discount"] * 100))) <= set(
        range(0, 11))
    assert table["l_tax"].max() <= 0.08
    price = table["l_quantity"] * GEN.retail_price_cents(
        table["l_partkey"]) / 100.0
    np.testing.assert_array_equal(table["l_extendedprice"], price)
    ship, commit, receipt = (table[k] for k in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    assert ship.min() >= 1 and receipt.max() <= GEN.ORDER_DATE_LAST + 151
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    flag = table["l_returnflag"]
    assert np.all((flag == b"N") == (receipt > GEN.CURRENT_DATE))
    assert np.all((table["l_linestatus"] == b"O") == (ship > GEN.CURRENT_DATE))
    assert set(np.unique(table["l_shipmode"])) <= set(GEN.SHIPMODE)
    lens = np.char.str_len(table["l_comment"])
    assert lens.min() >= 10 and lens.max() <= 43


def test_lineitem_keys_follow_the_spec(table):
    okey = table["l_orderkey"]
    assert np.all(np.diff(okey) >= 0)        # lines of an order together
    assert np.all((okey - 1) % 32 < 8)       # sparse: 8 of every 32
    p, supps = table["l_partkey"].astype(np.int64), 50
    offs = [(p + i * (supps // 4 + (p - 1) // supps)) % supps + 1
            for i in range(4)]
    assert np.all(np.any(np.stack(offs) == table["l_suppkey"], axis=0))


def test_lineitem_is_a_function_of_the_seed():
    a = GEN.generate({"rows": 2000, "scale_factor": 0.001}, SEED)
    b = GEN.generate({"rows": 2000, "scale_factor": 0.001}, SEED)
    c = GEN.generate({"rows": 2000, "scale_factor": 0.001}, SEED + 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l_partkey"], c["l_partkey"])


@pytest.mark.parametrize("mix", ["select_10pct", "range_10k", "price_agg"])
def test_reference_passes_the_truth_and_fails_a_perturbed_answer(table, mix):
    traffic = run.load_cell(f"lineitem_sf1.{mix}", run.load_spec(ROOT))[3]
    filters = scan.thresholds(traffic, table)
    rows = next(scan.requests(traffic, len(table["l_orderkey"]), SEED))
    want = REF.answer(table, traffic, filters, rows)
    ok = REF.check(table, traffic, filters, [(rows, want)])
    assert all(c["value"] <= c["limit"] for c in ok.values())
    bad = {k: np.array(v, copy=True) if isinstance(v, np.ndarray) else v
           for k, v in want.items()}
    if traffic.get("aggregates"):
        bad["count(l_extendedprice)"] += 1
    else:
        bad["l_quantity"][3] += 1
    got = REF.check(table, traffic, filters, [(rows, bad)])
    assert any(c["value"] > c["limit"] for c in got.values())


@pytest.mark.parametrize("mix", ["select_10pct", "range_10k", "price_agg"])
def test_float32_control_fails_the_comparison(mix):
    line, = control.main(["--workload", f"lineitem_sf1.{mix}", "--seeds",
                          str(SEED), "--requests", "3"], config_override=TINY)
    worst = line["control_float32"]
    assert any(worst[k] > REF.LIMITS[k] for k in worst), worst


def cell(mix: str, **kw):
    return run.main(["--workload", f"lineitem_sf1.{mix}", "--seed", str(SEED),
                     "--seconds", "0.3", "--trace", "0"], allow_cpu=True,
                    config_override=TINY, **kw)


@pytest.mark.parametrize("mix", ["select_10pct", "range_10k", "price_agg"])
def test_scan_cell_runs_correct_at_a_tiny_size(mix):
    res = cell(mix)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}


def test_device_decode_path_runs_correct_in_interpret_mode():
    from repro.core import format as fmt
    fmt.set_bitunpack_backend("device")
    try:
        res = run.main(["--workload", "lineitem_sf1.range_10k", "--seed",
                        str(SEED), "--seconds", "0.1", "--trace", "0"],
                       allow_cpu=True, config_override={
                           "data": {"rows": 3000, "unit_rows": 256,
                                    "scale_factor": 0.001},
                           "store": {"object_bytes": 120_000}})
    finally:
        fmt.set_bitunpack_backend("auto")
    assert res["correct"]


def _altered_decode(monkeypatch):
    from repro.core import format as fmt
    real = fmt._resolve_bitunpack()

    def altered(words, bits, n):
        out = np.array(real(words, bits, n), copy=True)
        out[n // 2] ^= 1  # one value, flipped where it is produced
        return out
    monkeypatch.setattr(fmt, "_resolve_bitunpack", lambda: altered)


def test_an_altered_decoded_value_makes_the_table_cells_incorrect(
        monkeypatch):
    _altered_decode(monkeypatch)
    for mix in ("select_10pct", "range_10k"):
        res = cell(mix)
        assert not res["correct"], mix
        assert res["checks"]["mismatched_cells"]["value"] > 0


def test_an_altered_aggregate_makes_price_agg_incorrect(monkeypatch):
    from repro.core import objclass as oc
    real = oc._agg_local

    def altered(table, col, fn):
        out = real(table, col, fn)
        if fn == "sum":
            out = {"sum": out["sum"] * (1 + 1e-9)}
        return out
    monkeypatch.setattr(oc, "_agg_local", altered)
    res = cell("price_agg")
    assert not res["correct"]
    assert res["checks"]["sum_rel_gap"]["value"] > \
        res["checks"]["sum_rel_gap"]["limit"]
