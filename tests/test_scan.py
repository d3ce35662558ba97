"""Composable scan surface: builder→PhysicalPlan→engine, pushed-down
OSD pruning vs client pruning, server-side table concat, and the
unified stats emission.  Example-based on purpose: must run without
hypothesis."""

import numpy as np
import pytest

from repro.core import (Column, GlobalVOL, LogicalDataset, PartitionPolicy,
                        RowRange, Scan, make_store)
from repro.core import expr as ex
from repro.core import format as fmt
from repro.core import objclass as oc
from repro.core import scan as sc


def make_world(n=4000, n_osds=5, replicas=3, seed=0):
    rng = np.random.default_rng(seed)
    ds = LogicalDataset(
        "t", (Column("x", "float64"), Column("y", "int32")), n, 64)
    store = make_store(n_osds, replicas=replicas)
    vol = GlobalVOL(store)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=8 << 10,
                                          max_object_bytes=8 << 12))
    table = {"x": rng.normal(size=n),
             "y": rng.integers(0, 1000, n).astype(np.int32)}
    vol.write(omap, table)
    return store, vol, omap, table


def batch_results(store, names, ops):
    """The batch plane's results placed at their input positions."""
    return sc._by_position(len(names), store.exec_batch_iter(names, ops))


IMPOSSIBLE = [oc.op("filter", col="y", cmp=">", value=2000),
              oc.op("agg", col="x", fn="count")]


# ---------------------------------------------------------------- builder
def test_builder_is_immutable_and_composable():
    store, vol, omap, table = make_world()
    base = vol.scan("t").filter("y", "<", 500)
    a = base.agg("sum", "x")
    b = base.project("x")
    assert base.aggregates == () and base.projection is None  # untouched
    ra, _ = a.execute()
    rb, _ = b.execute()
    mask = table["y"] < 500
    assert ra == pytest.approx(table["x"][mask].sum(), rel=1e-12)
    assert np.array_equal(rb["x"], table["x"][mask])


def test_builder_multi_filter_conjunction():
    store, vol, omap, table = make_world()
    res, stats = (vol.scan("t").filter("y", ">", 100)
                  .filter("y", "<", 300).filter("x", ">", 0.0)
                  .agg("count", "x").execute())
    mask = (table["y"] > 100) & (table["y"] < 300) & (table["x"] > 0)
    assert res == float(mask.sum())
    assert stats["pushdown"] and stats["exec_class"] == sc.EXEC_OSD_COMBINE


def test_builder_multi_aggregate_one_partial_per_osd():
    store, vol, omap, table = make_world()
    s = (vol.scan("t").filter("y", "<", 500)
         .agg("sum", "x").agg("count", "x").agg("min", "x")
         .agg("max", "x").agg("mean", "x"))
    assert s.pipeline()[-1].name == "multi_agg"
    assert oc.pipeline_mergeable(s.pipeline())
    store.fabric.reset()
    res, stats = s.execute()
    sel = table["x"][table["y"] < 500]
    assert res["sum(x)"] == pytest.approx(sel.sum(), rel=1e-12)
    assert res["count(x)"] == float(sel.size)
    assert res["min(x)"] == pytest.approx(sel.min(), rel=1e-12)
    assert res["max(x)"] == pytest.approx(sel.max(), rel=1e-12)
    assert res["mean(x)"] == pytest.approx(sel.mean(), rel=1e-12)
    primaries = {store.cluster.primary(e.name) for e in omap}
    assert stats["rx_frames"] == len(primaries)  # ONE partial per OSD
    assert stats["result_rows"] == 1


def test_builder_rows_range_scan():
    store, vol, omap, table = make_world()
    res, stats = vol.scan("t").rows(123, 456).project("y").execute()
    assert np.array_equal(res["y"], table["y"][123:456])
    assert stats["exec_class"] == sc.EXEC_SERVER_CONCAT


def test_builder_rows_compose_with_tails():
    """A row range composes with every tail class.  The range ships as
    a shared ``row_slice`` op (resolved per object ON the OSD from its
    extent xattr), so a row-ranged aggregate rides the per-OSD combine
    plane — with pushed-down pruning — instead of per-object gathers."""
    store, vol, omap, table = make_world()
    s = vol.scan("t").rows(100, 2500).filter("y", "<", 500).agg("sum", "x")
    plan = s.explain()
    assert plan.exec_cls == sc.EXEC_OSD_COMBINE
    assert plan.prune == "pushdown"
    assert plan.pipelines is None          # ONE shared pipeline
    assert plan.ops[0].name == "row_slice"
    r, stats = s.execute()
    mask = table["y"][100:2500] < 500
    assert r == pytest.approx(table["x"][100:2500][mask].sum(), rel=1e-12)
    assert stats["xattr_ops"] == 0         # no client zone-map traffic
    m, _ = vol.scan("t").rows(0, 1000).median("x").execute()
    assert m == pytest.approx(float(np.median(table["x"][:1000])),
                              abs=1e-12)
    ma, _ = (vol.scan("t").rows(0, 1000).agg("sum", "x")
             .agg("count", "x").execute())
    assert ma["count(x)"] == 1000.0
    assert ma["sum(x)"] == pytest.approx(table["x"][:1000].sum(),
                                         rel=1e-12)
    # the client strategy still restricts itself to the row range: a
    # scan of the first object's rows never plans the rest
    first = omap.extents[0]
    plan = (vol.scan("t").rows(first.row_start, first.row_stop)
            .filter("y", "<", 500).agg("sum", "x").prune("client")
            .explain())
    assert plan.prune == "client"
    assert set(plan.names) | set(plan.pruned) == {first.name}


def test_builder_median_exact_vs_approx():
    store, vol, omap, table = make_world()
    med, st1 = vol.scan("t").median("x").execute()
    assert med == pytest.approx(float(np.median(table["x"])), abs=1e-12)
    assert st1["exec_class"] == sc.EXEC_HOLISTIC_GATHER
    assert st1["pushdown"] is False
    ap, st2 = vol.scan("t").median("x", approx=True).execute()
    assert st2["approx_rewrite"] and st2["pushdown"] is True
    assert st2["exec_class"] == sc.EXEC_OSD_COMBINE
    assert abs(ap - med) < 0.1


def test_builder_validation_errors():
    s = Scan(dataset="t")
    with pytest.raises(ValueError):
        s.filter("y", "~", 1)
    with pytest.raises(ValueError):
        s.agg("stddev", "x")
    with pytest.raises(ValueError):
        s.agg("sum", "x").median("x")
    with pytest.raises(ValueError):
        s.median("x").agg("sum", "x")
    with pytest.raises(ValueError):
        s.prune("osd")
    with pytest.raises(ValueError):
        s.execute()  # unbound


def test_explain_exposes_physical_plan():
    store, vol, omap, table = make_world()
    plan = vol.scan("t").filter("y", "<", 500).agg("sum", "x").explain()
    assert plan.exec_cls == sc.EXEC_OSD_COMBINE
    assert plan.prune == "pushdown"
    assert plan.predicates == ex.Cmp("y", "<", 500)
    assert len(plan.names) == omap.n_objects


# ------------------------------------------------------ multi-filter
def test_query_shim_multi_filter_end_to_end():
    store, vol, omap, table = make_world()
    res, stats = (vol.scan("t").filter("y", ">", 100).filter("y", "<", 300)
                  .agg("count", "x").execute())
    mask = (table["y"] > 100) & (table["y"] < 300)
    assert res == float(mask.sum())
    assert stats["pushdown"] and stats["result_rows"] == 1
    # conjunction prunes: a range wholly outside every zone map
    res, stats = (vol.scan("t").filter("y", ">", 100).filter("y", ">", 2000)
                  .agg("count", "x").execute())
    assert res == 0.0 and stats["objects_pruned"] == omap.n_objects


# ------------------------------------------------- OSD-side prune plane
def test_pushed_down_prune_issues_zero_zone_map_requests():
    store, vol, omap, table = make_world()
    store.fabric.reset()
    res, stats = vol.query(omap, [
        oc.op("filter", col="y", cmp="<", value=500),
        oc.op("agg", col="x", fn="sum")])
    assert res == pytest.approx(table["x"][table["y"] < 500].sum(),
                                rel=1e-12)
    assert store.fabric.xattr_ops == 0          # NO client zone-map reqs
    assert stats["xattr_ops"] == 0 and stats["prune"] == "pushdown"
    # a FRESH client is just as cold-start free
    fresh = GlobalVOL(store)
    store.fabric.reset()
    fresh.query(omap, IMPOSSIBLE)
    assert store.fabric.xattr_ops == 0


def test_osd_prune_equals_client_prune_sets_and_results():
    """The two strategies share one prune rule: same kept/pruned sets,
    bit-exact results, on identical metadata."""
    store, vol, omap, table = make_world()
    for flt in [("y", ">", 2000),     # prunes everything
                ("y", "<", 5),        # prunes most objects
                ("y", "<", 500),      # prunes nothing
                ("y", "==", 7)]:
        ops = [oc.op("filter", col=flt[0], cmp=flt[1], value=flt[2]),
               oc.op("agg", col="x", fn="sum")]
        r_osd, s_osd = vol.query(omap, ops, prune="pushdown")
        r_cli, s_cli = vol.query(omap, ops, prune="client")
        assert r_osd == r_cli, flt                      # bit-exact
        assert s_osd["objects_pruned"] == s_cli["objects_pruned"], flt
        assert s_osd["objects_touched"] == s_cli["objects_touched"], flt
        # and both match the unpruned ground truth
        r_none, _ = vol.query(omap, ops, prune="none")
        assert r_osd == r_none, flt


def test_osd_prune_table_out_preserves_row_order():
    store, vol, omap, table = make_world()
    ops = [oc.op("filter", col="y", cmp="<", value=30)]
    r_osd, s_osd = vol.query(omap, ops, prune="pushdown")
    mask = table["y"] < 30
    assert np.array_equal(r_osd["y"], table["y"][mask])  # ROW order
    assert np.array_equal(r_osd["x"], table["x"][mask])
    assert s_osd["result_rows"] == int(mask.sum())


def test_cross_client_rewrite_between_plan_and_execute():
    """A client-side prune decides at COMPILE time, so a rewrite landing
    between plan and execute slips through (the inherent TOCTOU).  The
    pushed-down prune decides ON the OSD at EXECUTE time against its
    current xattrs, so the same race cannot produce a stale result."""
    store, vol_a, omap, table = make_world()
    vol_b = GlobalVOL(store)
    n = len(table["y"])

    # compile both plans BEFORE the rewrite
    s_osd = vol_a.scan("t").filter("y", ">", 2000).agg("count", "x")
    s_cli = s_osd.prune("client")
    plan_osd = s_osd.explain(omap)
    plan_cli = s_cli.explain(omap)
    assert plan_osd.pruned == () and plan_osd.predicates  # decide later
    assert len(plan_cli.pruned) == omap.n_objects         # decided NOW

    # client B rewrites at the same epoch: now every row matches
    table2 = dict(table, y=(table["y"] + 5000).astype(np.int32))
    vol_b.write(omap, table2)

    r_osd, st = vol_a.engine.execute(plan_osd)
    assert r_osd == float(n)            # OSD saw the FRESH zone maps
    assert st["objects_pruned"] == 0
    r_cli, _ = vol_a.engine.execute(plan_cli)
    assert r_cli == 0.0                 # the stale window, demonstrated


# ------------------------------------------------- server-side concat
def test_filter_project_scan_returns_exactly_k_frames():
    store, vol, omap, table = make_world()
    primaries = {store.cluster.primary(e.name) for e in omap}
    assert omap.n_objects > len(primaries)  # N > K or the claim is vacuous
    store.fabric.reset()
    res, stats = vol.query(omap, [
        oc.op("filter", col="y", cmp="<", value=500),
        oc.op("project", cols=["x"])])
    assert stats["rx_frames"] == len(primaries)      # EXACTLY K frames
    assert stats["ops"] == len(primaries)
    mask = table["y"] < 500
    assert np.array_equal(res["x"], table["x"][mask])


def test_exec_concat_matches_exec_batch_bit_exact():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    ops = [oc.op("filter", col="y", cmp="<", value=500),
           oc.op("project", cols=["x", "y"])]
    pruned = []
    frames = list(store.exec_concat_iter(names, ops, pruned_out=pruned))
    assert not pruned
    parts = sc._split_frames(len(names), frames)
    blobs = batch_results(store, names, ops)
    for part, blob in zip(parts, blobs):
        ref = fmt.decode_block(blob)
        assert set(part) == set(ref)
        for k in ref:
            assert np.array_equal(part[k], ref[k])


def test_exec_concat_failover_to_replica_mid_batch():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    ops = [oc.op("project", cols=["y"])]
    expect = np.concatenate(
        [fmt.decode_block(b)["y"] for b in batch_results(store, names, ops)])
    victim = names[0]
    primary = store.cluster.primary(victim)
    with store.osds[primary].lock:
        del store.osds[primary].data[victim]
    store.fabric.reset()
    frames = list(store.exec_concat_iter(names, ops))
    primaries = {store.cluster.primary(n) for n in names}
    assert store.fabric.ops == len(primaries) + 1  # + one retry request
    parts = sc._split_frames(len(names), frames)
    got = np.concatenate([p["y"] for p in parts])
    assert np.array_equal(got, expect)


def test_exec_concat_rejects_partial_tails():
    store, vol, omap, table = make_world()
    with pytest.raises(ValueError):
        list(store.exec_concat_iter(omap.object_names(),
                                    [oc.op("agg", col="x", fn="sum")]))


def test_read_rides_concat_plane():
    store, vol, omap, table = make_world()
    store.fabric.reset()
    out = vol.read(omap, RowRange(100, 1300), columns=["y"])
    assert np.array_equal(out["y"], table["y"][100:1300])
    primaries = {store.cluster.primary(e.name) for e in omap}
    assert store.fabric.rx_frames <= len(primaries)


# ---------------------------------------------------- unified stats
def test_stats_drift_fixed_between_vol_and_driver():
    """Same scan, same stats: the holistic+approx rewrite reports the
    same pushdown through vol.query as through Scan.execute."""
    store, vol, omap, table = make_world()
    _, vs = vol.query(omap, [oc.op("median", col="x")], allow_approx=True)
    _, ds = vol.scan("t").median("x", approx=True).execute()
    assert vs["pushdown"] is True and ds["pushdown"] is True
    assert vs["approx_rewrite"] and ds["exec_class"] == sc.EXEC_OSD_COMBINE
    _, vs2 = vol.query(omap, [oc.op("median", col="x")])
    _, ds2 = vol.scan("t").median("x").execute()
    assert vs2["pushdown"] is False and ds2["pushdown"] is False


def test_result_rows_never_none_for_completed_queries():
    store, vol, omap, table = make_world()
    _, s = vol.scan("t").agg("sum", "x").execute()
    assert s["result_rows"] == 1                  # scalar aggregate
    _, s = vol.scan("t").median("x").execute()
    assert s["result_rows"] == 1                  # holistic scalar
    _, s = vol.scan("t").agg("sum", "x").agg("count", "y").execute()
    assert s["result_rows"] == 1                  # one aggregate row
    _, s = vol.scan("t").filter("y", "<", 50).project("x").execute()
    assert s["result_rows"] == int((table["y"] < 50).sum())
    _, s = vol.engine.execute(vol.engine.compile_ops(
        omap, [oc.op("agg", col="x", fn="sum")], baseline=True))
    assert s["result_rows"] == 1                  # baseline, unified too


def test_driver_and_vol_execute_identical_plans():
    store, vol, omap, table = make_world()
    q = vol.scan("t").filter("y", "<", 300).agg("mean", "x")
    r1, s1 = q.execute()
    r2, vs = vol.query(omap, q.pipeline())
    assert r1 == pytest.approx(r2, rel=1e-15)
    assert s1["exec_class"] == vs["exec_class"]
    assert s1["prune"] == vs["prune"]
    assert s1["ops"] == vs["ops"]
    assert s1["rx_frames"] == vs["rx_frames"]


def test_driver_table_out_preserves_row_order():
    store, vol, omap, table = make_world()
    res, _ = vol.scan("t").filter("y", "<", 50).project("x").execute()
    assert np.array_equal(res["x"], table["x"][table["y"] < 50])


def test_driver_executes_scans_directly():
    store, vol, omap, table = make_world()
    res, stats = (Scan(dataset="t").bind(vol).filter("y", "<", 500)
                  .agg("count", "x").execute())
    assert res == float((table["y"] < 500).sum())
    assert stats["exec_class"] == sc.EXEC_OSD_COMBINE


# ------------------------------------------- expression operands
PRICE_TIMES_DISC = ex.Col("x") * (1 - ex.Col("y"))


@pytest.mark.parametrize("specs", [
    (("sum", PRICE_TIMES_DISC),),
    (("sum", PRICE_TIMES_DISC), ("count", "y"), ("max", PRICE_TIMES_DISC),
     ("sum", "x")),
], ids=["one", "mixed"])
def test_expression_operand_merges_and_combines(specs):
    """agg / multi_agg over a value expression: partials from the local
    op fold through merge_partials and combine_partials like a
    column's, keyed by the expression's canonical text."""
    ops = [oc.op("filter", col="y", cmp="<", value=500),
           oc.op("agg", col=specs[0][1].to_json(), fn=specs[0][0])
           if len(specs) == 1 else
           oc.op("multi_agg", specs=tuple((fn, c if isinstance(c, str)
                                           else c.to_json())
                                          for fn, c in specs))]
    rng = np.random.default_rng(9)
    tabs = [{"x": rng.normal(size=100),
             "y": rng.integers(0, 1000, 100).astype(np.int32),
             "z": rng.normal(size=100)} for _ in range(4)]
    parts = [oc.apply_pipeline(t, ops) for t in tabs]
    merged = oc.merge_partials(ops, parts[:2])
    direct = oc.combine_partials(ops, parts)
    via_merge = oc.combine_partials(ops, [merged, *parts[2:]])
    assert direct == pytest.approx(via_merge, rel=1e-12)
    m = np.concatenate([t["y"] < 500 for t in tabs])
    x = np.concatenate([t["x"] for t in tabs])[m]
    y = np.concatenate([t["y"] for t in tabs])[m]
    want = {"sum(x * (1.0 - y))": (x * (1.0 - y)).sum(),
            "max(x * (1.0 - y))": (x * (1.0 - y)).max(),
            "count(y)": float(m.sum()), "sum(x)": x.sum()}
    if len(specs) == 1:
        assert direct == pytest.approx(want["sum(x * (1.0 - y))"], rel=1e-12)
    else:
        assert direct == pytest.approx(want, rel=1e-12)


def test_pipeline_digest_tells_operands_apart_and_keeps_column_digests():
    """Column operands digest as they always have (pinned), and two
    different expression operands digest differently."""
    price = Scan(dataset="lineitem").filter("l_extendedprice", ">", 95000.0)
    for fn, col in (("count", "l_extendedprice"), ("sum", "l_extendedprice"),
                    ("min", "l_extendedprice"), ("max", "l_extendedprice"),
                    ("sum", "l_quantity")):
        price = price.agg(fn, col)
    one = Scan(dataset="t").filter("y", "<", 500).agg("mean", "x")
    assert oc.pipeline_digest(price.pipeline()) == \
        "8496e69c089bf7659fe34bfdddf19b893cd77bcd"
    assert oc.pipeline_digest(one.pipeline()) == \
        "ff1f51732742e2722177db505f895a6f14ef463e"
    assert oc.pipeline_digest([oc.op("agg", col="x", fn="sum")]) == \
        "900f877816b4882fd1b69d3d22d19cd2e3ad839c"
    base = Scan(dataset="t").filter("y", "<", 500)
    digests = {oc.pipeline_digest(base.agg("sum", e).pipeline())
               for e in (ex.Col("x") * ex.Col("y"), ex.Col("y") * ex.Col("x"),
                         ex.Col("x") + ex.Col("y"), ex.Col("x") * 2,
                         ex.Col("x") * 3, "x")}
    assert len(digests) == 6
    # the wire form and its parsed twin digest alike
    ops = base.agg("sum", ex.Col("x") * ex.Col("y")).pipeline()
    parsed = [oc.ObjOp(o.name, dict(o.params, col=ex.value_from_json(
        o.params["col"]))) if o.name == "agg" else o for o in ops]
    assert oc.pipeline_digest(parsed) == oc.pipeline_digest(ops)


def test_expression_aggregate_scan_runs_on_the_osd_combine_plane():
    store, vol, omap, table = make_world()
    expr = ex.Col("x") * (1 - ex.Col("y"))
    s = vol.scan("t").filter_expr(ex.Between("y", 100, 400)).agg("sum", expr)
    plan = s.explain()
    assert plan.exec_cls == sc.EXEC_OSD_COMBINE
    assert [o.name for o in plan.exec_ops] == ["filter", "agg"]
    res, stats = s.execute()
    m = (table["y"] >= 100) & (table["y"] <= 400)
    assert res == pytest.approx(
        (table["x"][m] * (1.0 - table["y"][m])).sum(), rel=1e-12)
    # evaluated on the OSDs over the rows that pass, one partial each
    assert stats["expr_rows"] == int(m.sum())
    primaries = {store.cluster.primary(n) for n in omap.object_names()}
    assert stats["ops"] == len(primaries)
    # several aggregates keep the expression's name as their key
    res2, _ = vol.scan("t").filter_expr(ex.Between("y", 100, 400)) \
        .agg("sum", expr).agg("count", "x").execute()
    assert res2["sum(x * (1.0 - y))"] == pytest.approx(res, rel=1e-12)
    assert res2["count(x)"] == float(m.sum())
    with pytest.raises(ValueError, match="median"):
        vol.scan("t").agg("median", expr)


# ----------------------------------------------------- multi_agg op
def test_multi_agg_column_pruning_and_merge():
    specs = (("sum", "x"), ("count", "y"))
    ops = [oc.op("filter", col="y", cmp="<", value=500),
           oc.op("multi_agg", specs=specs)]
    assert oc.required_columns(ops) == ["x", "y"]
    rng = np.random.default_rng(5)
    tabs = [{"x": rng.normal(size=100),
             "y": rng.integers(0, 1000, 100).astype(np.int32),
             "z": rng.normal(size=100)} for _ in range(3)]
    parts = [oc.get_impl("multi_agg").local(
        oc.get_impl("filter").local(t, col="y", cmp="<", value=500),
        specs=specs) for t in tabs]
    merged = oc.merge_partials([oc.op("multi_agg", specs=specs)], parts)
    direct = oc.combine_partials([oc.op("multi_agg", specs=specs)], parts)
    via_merge = oc.combine_partials(
        [oc.op("multi_agg", specs=specs)], [merged])
    assert direct == pytest.approx(via_merge, rel=1e-12)
    allx = np.concatenate([t["x"][t["y"] < 500] for t in tabs])
    assert direct["sum(x)"] == pytest.approx(allx.sum(), rel=1e-12)
    assert direct["count(y)"] == float(allx.size)
