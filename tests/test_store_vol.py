"""Object store + VOL + objclass behaviour (paper §2 goals 1-3)."""

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import (Column, GlobalVOL, LogicalDataset, PartitionPolicy,
                        RowRange, make_store)
from repro.core import objclass as oc
from repro.core.store import ObjectNotFound


def make_world(n=2000, n_osds=6, replicas=3, seed=0, obj_kb=8):
    rng = np.random.default_rng(seed)
    ds = LogicalDataset(
        "t", (Column("x", "float64"), Column("y", "int32")), n, 64)
    store = make_store(n_osds, replicas=replicas)
    vol = GlobalVOL(store)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=obj_kb << 10,
                                          max_object_bytes=obj_kb << 12))
    table = {"x": rng.normal(size=n),
             "y": rng.integers(0, 1000, n).astype(np.int32)}
    vol.write(omap, table)
    return store, vol, omap, table


# ---------------------------------------------------------------- store
def test_put_get_replication_failover():
    store = make_store(5, replicas=3)
    store.put("obj", b"hello")
    assert store.get("obj") == b"hello"
    # kill primary AND second replica; read must still succeed
    acting = store.cluster.locate("obj")
    store.fail_osd(acting[0])
    store.fail_osd(acting[1])
    assert store.get("obj") == b"hello"
    store.fail_osd(store.cluster.locate("obj")[0])
    with pytest.raises((ObjectNotFound, RuntimeError, KeyError)):
        store.get("obj")


def test_recovery_restores_replication():
    store, vol, omap, table = make_world()
    victim = store.cluster.locate(omap.object_names()[0])[0]
    store.fail_osd(victim)
    rec = store.recover()
    assert rec["objects_lost"] == 0
    # every object now has a full acting set
    for name in omap.object_names():
        for osd_id in store.cluster.locate(name):
            assert name in store.osds[osd_id].data


def test_exec_runs_on_surviving_replica():
    store, vol, omap, table = make_world()
    name = omap.object_names()[0]
    store.fail_osd(store.cluster.locate(name)[0])
    res = store.exec(name, [oc.op("agg", col="y", fn="count")])
    assert res["count"] > 0


# ---------------------------------------------------------------- vol
def test_read_equals_slice():
    store, vol, omap, table = make_world()
    out = vol.read(omap, RowRange(123, 456))
    assert np.allclose(out["x"], table["x"][123:456])
    assert np.array_equal(out["y"], table["y"][123:456])


def test_read_projection_moves_fewer_bytes():
    store, vol, omap, table = make_world()
    store.fabric.reset()
    vol.read(omap, RowRange(0, 1000), columns=["y"])
    rx_proj = store.fabric.client_rx
    store.fabric.reset()
    vol.read(omap, RowRange(0, 1000))
    rx_all = store.fabric.client_rx
    assert rx_proj < rx_all / 2


@given(st.sampled_from(["sum", "count", "min", "max", "mean"]),
       st.sampled_from(["<", "<=", ">", ">=", "==", "!="]),
       st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_pushdown_agg_matches_numpy(fn, cmp, thr):
    store, vol, omap, table = make_world()
    res, stats = vol.query(omap, [
        oc.op("filter", col="y", cmp=cmp, value=thr),
        oc.op("agg", col="x", fn=fn)])
    mask = {"<": np.less, "<=": np.less_equal, ">": np.greater,
            ">=": np.greater_equal, "==": np.equal,
            "!=": np.not_equal}[cmp](table["y"], thr)
    sel = table["x"][mask]
    expect = {"sum": sel.sum() if sel.size else 0.0,
              "count": float(sel.size),
              "min": sel.min() if sel.size else np.inf,
              "max": sel.max() if sel.size else -np.inf,
              "mean": sel.mean() if sel.size else 0.0}[fn]
    assert res == pytest.approx(expect, rel=1e-9, abs=1e-12)
    assert stats["pushdown"]


def test_holistic_median_exact_and_approx():
    # enough rows per object that the fixed sketch cost (bins * 4 B per
    # object) clearly beats the gather — the crossover the paper's §3.2
    # "acceptable approximations" tradeoff is about
    store, vol, omap, table = make_world(n=30_000, obj_kb=64)
    med, st1 = vol.query(omap, [oc.op("median", col="x")])
    assert med == pytest.approx(float(np.median(table["x"])), abs=1e-12)
    approx, st2 = vol.query(omap, [oc.op("median", col="x")],
                            allow_approx=True)
    assert st2["approx_rewrite"]
    assert abs(approx - med) < 0.02
    # the decomposable rewrite moves far fewer bytes than the gather
    assert st2["client_rx"] < st1["client_rx"] / 3


def test_zone_map_pruning_sound_and_effective():
    store, vol, omap, table = make_world()
    # impossible predicate: everything pruned, count = 0
    res, stats = vol.query(omap, [
        oc.op("filter", col="y", cmp=">", value=10_000),
        oc.op("agg", col="x", fn="count")])
    assert res == 0.0 and stats["objects_pruned"] == omap.n_objects
    # sound: pruned plan result == unpruned result for a selective filter
    res2, _ = vol.query(omap, [
        oc.op("filter", col="y", cmp="<", value=5),
        oc.op("agg", col="x", fn="count")])
    assert res2 == float((table["y"] < 5).sum())


def test_pushdown_vs_clientside_same_result_fewer_bytes():
    store, vol, omap, table = make_world()
    q = vol.scan("t").filter("y", "<", 300).agg("mean", "x")
    r1, s1 = q.execute()
    r2, s2 = vol.engine.execute(
        vol.engine.compile_ops(omap, q.pipeline(), baseline=True))
    assert r1 == pytest.approx(r2, rel=1e-12)
    assert s1["client_rx"] < s2["client_rx"] / 20
    assert s1["pushdown"] and not s2["pushdown"]


def test_driver_table_pipeline_roundtrip():
    store, vol, omap, table = make_world()
    res, stats = vol.query(omap, [
        oc.op("filter", col="y", cmp="<", value=50),
        oc.op("project", cols=["x"])])
    expect = table["x"][table["y"] < 50]
    assert sorted(res["x"].tolist()) == sorted(expect.tolist())


def test_local_vol_physical_design_counter():
    store, vol, omap, table = make_world()
    for _ in range(10):
        vol.query(omap, [oc.op("agg", col="x", fn="sum")])
    assert vol.local.preferred_layout() == "col"
