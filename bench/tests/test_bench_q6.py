"""TPC-H Q6 (cell ``lineitem_sf1.q6``): the program through
``GlobalVOL.scan`` against the plain reference over substitution draws,
the cell end to end at a tiny size on the CPU (the harness's look for
a chip skipped), faults planted in the timed path, and the cell's
per-layer readers on a synthetic trace."""

from __future__ import annotations

import dataclasses
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import bench  # noqa: E402
from bench import load_module, run, trace_reduce as tr  # noqa: E402
from bench.drivers import scan  # noqa: E402

QUERY = load_module(ROOT / "bench/drivers/query.py", "q6_driver")
REF = load_module(ROOT / "bench/configs/tpch_lineitem_sf1_q6_ref.py",
                  "ref_q6")
GEN = scan.load_generator("tpch_lineitem")
CELL = "lineitem_sf1.q6"
SPEC = run.load_spec(ROOT)
CFG = run.load_cell(CELL, SPEC, ROOT)[2]
TINY = {"data": {"rows": 12_000, "unit_rows": 256, "scale_factor": 0.002},
        "store": {"object_bytes": 200_000}}
SEED = 2 ** 31 + 11  # past what 32 signed bits hold
# the discounts whose float-arithmetic bounds drop rows: 0.06 + 0.01,
# 0.07 - 0.01 and 0.09 + 0.01 miss 0.07, 0.06 and 0.10
FLOAT_BOUND_MISSES = {6, 7, 9}


@pytest.fixture(scope="module")
def world():
    from repro.core import (Column, GlobalVOL, LogicalDataset,
                            PartitionPolicy, make_store)
    table = GEN.generate({"rows": 30_000, "scale_factor": 0.005}, SEED)
    store = make_store(8, replicas=3)
    vol = GlobalVOL(store)
    ds = LogicalDataset("lineitem", tuple(Column(k, v.dtype.str)
                                          for k, v in table.items()),
                        n_rows=30_000, unit_rows=256)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=400_000))
    vol.write(omap, table)
    yield store, vol, omap, table
    store.close()


def test_substitution_draws_follow_clause_2_4_6_3():
    draws = [r for r, _ in zip(QUERY.requests(CFG["query"], SEED),
                               range(2000))]
    years, discounts, quantities = (set(c) for c in zip(*draws))
    assert years == set(range(1993, 1998))
    assert discounts == set(range(2, 10))
    assert quantities == {24, 25}
    again = [r for r, _ in zip(QUERY.requests(CFG["query"], SEED), range(50))]
    other = [r for r, _ in zip(QUERY.requests(CFG["query"], SEED + 1),
                               range(50))]
    assert again == draws[:50] and other != again


@pytest.mark.parametrize("req", [(1993, 2, 24), (1994, 6, 25),
                                 (1995, 7, 24), (1996, 9, 25),
                                 (1997, 5, 24)], ids=str)
def test_q6_through_the_front_door_matches_the_reference(world, req):
    store, vol, omap, table = world
    got, stats = QUERY.q6(vol, "lineitem", req, GEN.EPOCH).execute()
    want = REF.answer(table, req)
    assert want > 0
    assert abs(got - want) / want <= REF.LIMITS["revenue_rel_gap"]
    # evaluated on the OSDs over the rows that pass, one partial each
    assert stats["exec_class"] == "osd-combine"
    assert stats["expr_rows"] == int(REF.passing(table, req).sum())
    assert stats["ops"] == len({store.cluster.primary(n)
                                for n in omap.object_names()})
    # the float32 control fails the comparison
    f32 = REF.answer(table, req, float_dtype=np.float32)
    assert abs(f32 - want) / want > REF.LIMITS["revenue_rel_gap"]


def cell(trace: int = 0):
    return run.main(["--workload", CELL, "--seed", str(SEED),
                     "--seconds", "0.3", "--trace", str(trace)],
                    allow_cpu=True, config_override=TINY)


def test_q6_cell_runs_correct_at_a_tiny_size():
    res = cell()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "scan_rows_per_s"}
    assert set(res["checks"]) == {"revenue_rel_gap",
                                  "under_replicated_objects"}
    assert list(res)[-1] == "checks"


def _float_bounds(pred, req):
    """The discount bounds as float arithmetic, DISCOUNT -+ 0.01."""
    kids = list(pred.children)
    d = req[1] / 100
    kids[2] = dataclasses.replace(kids[2], lo=d - 0.01, hi=d + 0.01)
    return dataclasses.replace(pred, children=tuple(kids))


def _ship_through_year_end(pred, req):
    """``l_shipdate <= `` the next year's first day, in place of ``<``."""
    kids = list(pred.children)
    kids[1] = dataclasses.replace(kids[1], cmp="<=")
    return dataclasses.replace(pred, children=tuple(kids))


def plant(monkeypatch, fault: str) -> None:
    from repro.core import expr as ex
    from repro.core.store import OSD
    if fault == "float32_products":
        monkeypatch.setitem(ex.ARITH_TABLE, "*", lambda a, b: np.multiply(
            np.asarray(a, np.float32), np.asarray(b, np.float32)))
        return
    if fault == "dropped_partial":
        real = OSD._serve_item
        first: dict = {}

        def dropping(self, name, ops, kind, *a, **kw):
            if kind == "combine" and first.setdefault("name", name) == name:
                return "skip", None, 0  # its partial never reaches merge
            return real(self, name, ops, kind, *a, **kw)
        monkeypatch.setattr(OSD, "_serve_item", dropping)
        return
    edit = {"float_discount_bounds": _float_bounds,
            "shipdate_le": _ship_through_year_end}[fault]
    real_pred = QUERY.q6_predicate
    monkeypatch.setattr(QUERY, "q6_predicate",
                        lambda req, epoch: edit(real_pred(req, epoch), req))
    real_load = bench.load_module
    monkeypatch.setattr(bench, "load_module", lambda path, name: QUERY
                        if pathlib.Path(path).name == "query.py"
                        else real_load(path, name))


@pytest.mark.parametrize("fault", ["float32_products", "shipdate_le",
                                   "float_discount_bounds",
                                   "dropped_partial"])
def test_a_planted_fault_makes_the_cell_incorrect(monkeypatch, fault):
    if fault == "float_discount_bounds":  # the seed draws such a discount
        first = [r for r, _ in zip(QUERY.requests(CFG["query"], SEED),
                                   range(4))]
        assert {d for _, d, _ in first} & FLOAT_BOUND_MISSES
    plant(monkeypatch, fault)
    res = cell()
    gap = res["checks"]["revenue_rel_gap"]
    assert not res["correct"] and gap["value"] > gap["limit"], res["checks"]


MS = 1_000_000  # ns


def test_the_cells_per_layer_readers_read_a_synthetic_trace():
    names = [m["name"] for m in run.metrics_for(CELL, SPEC["per_layer"])]
    assert names == ["expr_ms_per_scan", "decode_ms_per_scan.q6",
                     "bitunpack_roofline.q6", "device_idle.q6"]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [("bitunpack.3", 30 * MS, 2 * MS),
                                           ("bitunpack.4", 60 * MS, 2 * MS)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("window", 10 * MS, 100 * MS),
            ("osd.decode", 20 * MS, 10 * MS), ("osd.expr", 35 * MS, 1 * MS),
            ("osd.decode", 50 * MS, 10 * MS), ("osd.expr", 65 * MS, 1 * MS)]}]},
    ]
    obs = {"trace": tr.reduce(planes), "window_s": 0.1,
           "counters": {"requests": 2, "bitunpack_bytes": 1.0e6},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = {"expr_ms_per_scan": 1.0, "decode_ms_per_scan.q6": 10.0,
            "bitunpack_roofline.q6": 100 * 1.0e6 / 819e9 / 0.004,
            "device_idle.q6": 96.0}
    for name in names:
        read = load_module(run.reader_file(name, ROOT),
                           f"q6_metric_{name}").read
        assert read(obs) == pytest.approx(want[name]), name
    # a program that opens no ``osd.expr`` span reads nothing, and fails
    # nothing
    none = dict(obs, trace=dict(obs["trace"], spans={}))
    assert load_module(run.reader_file("expr_ms_per_scan", ROOT),
                       "q6_metric_none").read(none) is None


def test_benchmark_gains_the_cell_its_config_and_metrics():
    cells = {w["name"]: w for w in SPEC["workloads"]}
    assert cells[CELL] | {"why": ""} == {
        "name": CELL, "config": "tpch_lineitem_sf1_q6", "traffic": "q6",
        "chips": 1, "why": ""}
    conf = {c["name"]: c for c in SPEC["configs"]}["tpch_lineitem_sf1_q6"]
    assert conf["reduced"] == [] and CFG["reduced"] == []
    base = run.load_cell("lineitem_sf1.price_agg", SPEC, ROOT)[2]
    for key in ("generator", "data", "store", "guarantees", "precision"):
        assert CFG[key] == base[key], key
    e2e = [m["name"] for m in run.metrics_for(CELL, SPEC["end_to_end"])]
    assert e2e == ["setup_s", "scan_rows_per_s"]
