"""Expression-tree predicate plane: OR/IN/NOT/Between/StrPrefix
evaluated ON the OSDs, sound interval pruning shared bit-exactly by the
client planner and the pushed-down strategy, OSD-resolved row ranges
(``row_slice``), and the single comparator table all three layers
derive from.  Property tests ride the hypothesis shim (they skip
cleanly when hypothesis is missing)."""

import json

import numpy as np
import pytest

from repro.core import (Column, GlobalVOL, LogicalDataset, PartitionPolicy,
                        RowRange, SkyhookDriver, make_store)
from repro.core import expr as ex
from repro.core import format as fmt
from repro.core import objclass as oc
from repro.core import scan as sc
from repro.core.store import OSD
from tests._hyp import given, settings, st


def make_world(n=4000, n_osds=5, replicas=3, seed=0, sorted_cols=False):
    """A dataset with a float, an int, and a STRING column; with
    ``sorted_cols`` the int/string columns are written in ascending
    order so every object's zone map is a tight interval (what makes
    Or-of-disjoint-ranges pruning observable)."""
    rng = np.random.default_rng(seed)
    ds = LogicalDataset(
        "t", (Column("x", "float64"), Column("y", "int32"),
              Column("tag", "<U8")), n, 64)
    store = make_store(n_osds, replicas=replicas)
    vol = GlobalVOL(store)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=8 << 10,
                                          max_object_bytes=8 << 12))
    y = (np.arange(n) * 1000 // n if sorted_cols
         else rng.integers(0, 1000, n)).astype(np.int32)
    tag = np.array([f"s{v:06d}" for v in
                    (np.arange(n) if sorted_cols
                     else rng.integers(0, n, n))], dtype="<U8")
    table = {"x": rng.normal(size=n), "y": y, "tag": tag}
    vol.write(omap, table)
    return store, vol, omap, table


# ------------------------------------------------------- end-to-end eval
def _cases(table):
    """(builder, row mask) pairs covering every expression node."""
    y, tag = table["y"], table["tag"]
    return [
        (lambda s: s.or_(("y", "<", 50), ("y", ">", 950)),
         (y < 50) | (y > 950)),
        (lambda s: s.isin("y", [3, 5, 7, 500]),
         np.isin(y, [3, 5, 7, 500])),
        (lambda s: s.filter_expr(ex.Not(ex.Cmp("y", "<", 500))),
         ~(y < 500)),
        (lambda s: s.filter_expr(ex.Between("y", 100, 200)),
         (y >= 100) & (y <= 200)),
        (lambda s: s.filter_expr(ex.StrPrefix("tag", "s000")),
         np.char.startswith(tag, "s000")),
        (lambda s: s.filter_expr(
            ex.Or((ex.And((ex.Cmp("y", ">", 100), ex.Cmp("y", "<", 200))),
                   ex.Cmp("y", "==", 7))) & ex.Cmp("x", ">", 0.0)),
         (((y > 100) & (y < 200)) | (y == 7)) & (table["x"] > 0)),
    ]


def test_expression_scans_match_client_filtering_bit_exact():
    """Every expression node, through the pushed-down plane vs the
    no-pushdown client baseline vs prune='none': identical rows."""
    store, vol, omap, table = make_world()
    drv = SkyhookDriver(vol, n_workers=3)
    for build, mask in _cases(table):
        s = build(vol.scan("t")).project("x", "y")
        r_push, st_push = s.execute()
        r_none, _ = s.prune("none").execute()
        r_base, _ = drv.execute_client_side(build(drv.scan("t"))
                                            .project("x", "y"))
        for k in ("x", "y"):
            assert np.array_equal(r_push[k], table[k][mask])
            assert np.array_equal(r_none[k], table[k][mask])
            assert np.array_equal(r_base[k], table[k][mask])
        assert st_push["prune"] == "pushdown"


def test_or_in_scan_zero_zone_map_requests_and_k_frames():
    """The acceptance claim: an OR-group/IN-list scan with pushed-down
    pruning issues ZERO client zone-map requests and returns exactly K
    framed responses for K involved OSDs — even for a cold client."""
    store, vol, omap, table = make_world()
    primaries = {store.cluster.primary(e.name) for e in omap}
    assert omap.n_objects > len(primaries)  # N > K or the claim is weak
    fresh = GlobalVOL(store)
    store.fabric.reset()
    res, stats = (fresh.scan("t").or_(("y", "<", 50), ("y", ">", 950))
                  .isin("tag", ["s000003"]).project("x").execute(omap))
    mask = ((table["y"] < 50) | (table["y"] > 950)) \
        & np.isin(table["tag"], ["s000003"])
    assert np.array_equal(res["x"], table["x"][mask])
    assert store.fabric.xattr_ops == 0
    assert stats["rx_frames"] == len(primaries)
    assert stats["ops"] == len(primaries)
    assert stats["prune"] == "pushdown"


def test_driver_schedules_expression_scans():
    store, vol, omap, table = make_world()
    drv = SkyhookDriver(vol, n_workers=3)
    r, qs = drv.execute(drv.scan("t").or_(("y", "<", 10), ("y", ">", 990))
                        .agg("sum", "x"))
    mask = (table["y"] < 10) | (table["y"] > 990)
    assert r == pytest.approx(table["x"][mask].sum(), rel=1e-12)
    assert qs.prune == "pushdown"
    assert qs.exec_class == sc.EXEC_OSD_COMBINE


# ------------------------------------------------------- prune algebra
def test_or_of_disjoint_ranges_prunes_what_a_conjunction_cannot():
    """With sorted data every object's zone is a tight slice of the
    value space: Or(y<lo, y>hi) provably empties every MIDDLE object —
    a set no flat conjunction could prune — and both strategies prune
    the identical set."""
    store, vol, omap, table = make_world(sorted_cols=True)
    pred = ex.Or((ex.Cmp("y", "<", 100), ex.Cmp("y", ">", 900)))
    # ground truth from the stored zone maps themselves
    expect_pruned = sum(
        1 for e in omap
        if oc.zone_map_prunes(store.xattr(e.name)["zone_map"], pred))
    assert 0 < expect_pruned < omap.n_objects
    s = vol.scan("t").filter_expr(pred).agg("count", "x")
    r_osd, st_osd = s.execute()
    r_cli, st_cli = s.prune("client").execute()
    mask = (table["y"] < 100) | (table["y"] > 900)
    assert r_osd == r_cli == float(mask.sum())
    assert st_osd["objects_pruned"] == st_cli["objects_pruned"] \
        == expect_pruned
    # a middle object prunes because BOTH disjuncts empty it — the Or
    # rule (ALL children prune) at work; the flat plane could not even
    # express this query's rows as a conjunction
    mid = omap.extents[omap.n_objects // 2]
    zm = store.xattr(mid.name)["zone_map"]
    assert pred.prunes(zm)
    assert ex.Cmp("y", "<", 100).prunes(zm)
    assert ex.Cmp("y", ">", 900).prunes(zm)


def test_in_list_and_neq_prune_both_strategies_identically():
    store, vol, omap, table = make_world(sorted_cols=True)
    # IN-list wholly outside every zone: everything prunes, zero rows
    s = vol.scan("t").isin("y", [5000, 6000]).agg("count", "y")
    r_osd, st_osd = s.execute()
    r_cli, st_cli = s.prune("client").execute()
    assert r_osd == r_cli == 0.0
    assert st_osd["objects_pruned"] == st_cli["objects_pruned"] \
        == omap.n_objects
    # != prunes only constant zones (lo == value == hi)
    ds = LogicalDataset("const", (Column("y", "int32"),), 256, 8)
    vol2 = GlobalVOL(make_store(3, replicas=2))
    omap2 = vol2.create(ds, PartitionPolicy(target_object_bytes=256,
                                            max_object_bytes=1024))
    vol2.write(omap2, {"y": np.full(256, 7, np.int32)})
    r, stats = vol2.scan("const").filter("y", "!=", 7) \
                   .agg("count", "y").execute()
    assert r == 0.0
    assert stats["objects_pruned"] == omap2.n_objects
    r2, stats2 = (vol2.scan("const").filter("y", "!=", 8)
                  .agg("count", "y").execute())
    assert r2 == 256.0 and stats2["objects_pruned"] == 0


def test_str_prefix_prunes_on_string_zone_maps():
    store, vol, omap, table = make_world(sorted_cols=True)
    zm = store.xattr(omap.extents[0].name)["zone_map"]
    lo, hi = zm["tag"]
    assert isinstance(lo, str) and isinstance(hi, str)  # string bounds
    s = (vol.scan("t").filter_expr(ex.StrPrefix("tag", "s0000"))
         .project("tag"))
    r_osd, st_osd = s.execute()
    r_cli, st_cli = s.prune("client").execute()
    mask = np.char.startswith(table["tag"], "s0000")
    assert np.array_equal(r_osd["tag"], table["tag"][mask])
    assert np.array_equal(r_cli["tag"], table["tag"][mask])
    assert st_osd["objects_pruned"] == st_cli["objects_pruned"] > 0


def test_not_pushdown_prunes_residual_not_stays_conservative():
    store, vol, omap, table = make_world(sorted_cols=True)
    # ~(y < 5000) matches nothing; the prune payload is normalized
    # (De Morgan push-down), so it ships as y >= 5000 and every zone
    # map NOW proves its object empty — zero rows AND full pruning
    r, stats = (vol.scan("t").filter_expr(ex.Not(ex.Cmp("y", "<", 5000)))
                .agg("count", "y").execute())
    assert r == 0.0
    assert stats["objects_pruned"] == omap.n_objects
    # a negation normalize can't push down (Not over a non-empty In)
    # still never prunes — conservative: zero pruned, zero rows
    r, stats = (vol.scan("t")
                .filter_expr(ex.Not(ex.In("y", list(range(1000)))))
                .agg("count", "y").execute())
    assert r == 0.0
    assert stats["objects_pruned"] == 0
    assert stats["objects_touched"] == omap.n_objects


def test_legacy_triple_prune_payloads_still_work():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    ops = [oc.op("filter", col="y", cmp=">", value=5000),
           oc.op("agg", col="y", fn="count")]
    partials, pruned = store.exec_combine(
        names, ops, prune=(("y", ">", 5000),))
    assert not partials and set(pruned) == set(names)


# ------------------------------------------------------- row_slice plane
def _repartition_world():
    ds = LogicalDataset("rp", (Column("v", "int64"),), 200, 1)
    store = make_store(3, replicas=2)
    vol = GlobalVOL(store)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=800,
                                          max_object_bytes=1600))
    assert omap.n_objects == 2  # [0,100) and [100,200)
    v = np.arange(200, dtype=np.int64)
    vol.write(omap, {"v": v})
    return store, vol, omap, v


def _reput(store, vol, name, v, start, stop):
    part = {"v": v[start:stop]}
    store.put(name, vol.local.encode(part),
              {"zone_map": fmt.zone_map(part), "rows": [start, stop]})


def test_row_slice_resolves_against_current_extents():
    """The pushed-down row range: one compiled plan keeps serving the
    requested GLOBAL rows after the dataset is re-partitioned under it,
    because each OSD resolves the slice against its objects' CURRENT
    extent xattrs — not against the plan-time ObjectMap."""
    store, vol, omap, v = _repartition_world()
    a, b = omap.object_names()
    s = vol.scan("rp").rows(50, 150).project("v")
    plan = s.explain(omap)
    r0, _ = vol.engine.execute(plan)
    assert np.array_equal(r0["v"], v[50:150])
    # re-partition under the plan: boundary moves 100 -> 120
    _reput(store, vol, a, v, 0, 120)
    _reput(store, vol, b, v, 120, 200)
    r1, _ = vol.engine.execute(plan)
    # plan-time extents would have served v[50:100] + v[120:170]
    assert np.array_equal(r1["v"], v[50:150])


def test_row_slice_disjoint_extent_is_prune_equivalent():
    store, vol, omap, v = _repartition_world()
    a, b = omap.object_names()
    plan = vol.scan("rp").rows(0, 60).project("v").explain(omap)
    assert plan.names == (a,)  # compile-time targeting
    # swap the two objects' contents/extents under the compiled plan
    _reput(store, vol, a, v, 100, 200)
    _reput(store, vol, b, v, 0, 100)
    r, stats = vol.engine.execute(plan)
    assert r == {} or oc.table_n_rows(r) == 0
    assert stats["objects_pruned"] == 1
    assert stats["objects_touched"] == 0


def test_rows_aggregate_rides_combine_plane_zero_metadata():
    store, vol, omap, table = make_world()
    fresh = GlobalVOL(store)
    store.fabric.reset()
    s = (fresh.scan("t").rows(100, 2500).filter("y", "<", 500)
         .agg("sum", "x"))
    plan = s.explain(omap)
    assert plan.exec_cls == sc.EXEC_OSD_COMBINE
    assert plan.prune == "pushdown"
    assert plan.pipelines is None
    r, stats = fresh.engine.execute(plan)
    mask = table["y"][100:2500] < 500
    assert r == pytest.approx(table["x"][100:2500][mask].sum(), rel=1e-12)
    # still zero ZONE-MAP traffic; the single op is the row-slice
    # targeting refresh probing the .objmap version (a standalone
    # execute has no caller-held ObjectMap to vouch for currency —
    # every vol/scan/driver front end passes one and stays at zero)
    assert store.fabric.xattr_ops == 1


def test_row_sliced_scan_fails_over_to_replica():
    """An object missing from its primary must register as MISSING (and
    fail over to a replica) even though the pipeline carries a
    row_slice — absence is checked before extent resolution."""
    store, vol, omap, table = make_world(n_osds=4, replicas=3)
    victim = omap.extents[0].name
    primary = store.cluster.primary(victim)
    with store.osds[primary].lock:
        del store.osds[primary].data[victim]
        del store.osds[primary].xattrs[victim]
    out = vol.read(omap, RowRange(0, 1500), columns=["y"])
    assert np.array_equal(out["y"], table["y"][:1500])
    r, _ = (vol.scan("t").rows(0, 1500).filter("y", "<", 500)
            .agg("count", "y").execute())
    assert r == float((table["y"][:1500] < 500).sum())


def test_rows_past_dataset_end_is_empty_not_an_error():
    store, vol, omap, table = make_world()
    n = len(table["y"])
    r, stats = (vol.scan("t").rows(n + 200, n + 300)
                .agg("count", "y").execute())
    assert r == 0.0 and stats["objects_touched"] == 0
    out = vol.read(omap, RowRange(n + 200, n + 300), columns=["y"])
    assert out == {} or oc.table_n_rows(out) == 0


def test_row_slice_requires_extent_xattr():
    store = make_store(2, replicas=2)
    blob = fmt.encode_block({"v": np.arange(10)})
    store.put("bare", blob)  # no 'rows' xattr
    with pytest.raises(ValueError, match="extent"):
        store.exec("bare", [oc.op("row_slice", rows=(0, 5))])


def test_unresolved_row_slice_refuses_to_run():
    blob = fmt.encode_block({"v": np.arange(10)})
    with pytest.raises(ValueError, match="resolve"):
        oc.run_pipeline(blob, [oc.op("row_slice", rows=(0, 5))])
    resolved = oc.resolve_row_slice(
        [oc.op("row_slice", rows=(3, 30))], (5, 15))
    assert resolved[0].name == "select"
    assert resolved[0].params["rows"] == (0, 10)
    assert oc.resolve_row_slice(
        [oc.op("row_slice", rows=(20, 30))], (5, 15)) is None
    clamped = oc.resolve_row_slice(
        [oc.op("row_slice", rows=(20, 30))], (5, 15), clamp=True)
    assert clamped[0].params["rows"] == (0, 0)


def test_partial_gather_refuses_explicit_pushdown(monkeypatch):
    """Every BUILT-IN partial tail is mergeable now that row ranges
    ride the shared row_slice plane, so partial-gather only exists for
    extension ops whose tail has a combine but no associative merge.
    Register one: its positional responses carry no OSD prune info, so
    an EXPLICIT prune='pushdown' must refuse (not silently downgrade
    to the TOCTOU-prone client strategy), while 'auto' serves it via
    the client planner.  The op is registered for this test only: the
    registry pass of ``repro.analysis`` checks the real registry."""
    monkeypatch.setitem(oc._REGISTRY, "sum_nomerge", oc.OpImpl(
        lambda table, col: {"sum": np.asarray(
            table[col], np.float64).sum()},
        lambda parts, col: float(sum(p["sum"] for p in parts)),
        decomposable=True, table_out=False))  # merge=None
    store, vol, omap, table = make_world()
    ops = [oc.op("filter", expr=ex.Cmp("y", "<", 500).to_json()),
           oc.op("sum_nomerge", col="x")]
    plan = vol.engine.compile_ops(omap, ops)
    assert plan.exec_cls == sc.EXEC_PARTIAL_GATHER
    assert plan.prune == "client"  # auto fell back to the planner
    r, stats = vol.engine.execute(plan)
    assert r == pytest.approx(
        table["x"][table["y"] < 500].sum(), rel=1e-12)
    assert stats["exec_class"] == sc.EXEC_PARTIAL_GATHER
    with pytest.raises(ValueError, match="partial-gather"):
        vol.engine.compile_ops(omap, ops, prune="pushdown")


# ------------------------------------------------- one comparator table
def test_comparator_table_is_the_single_source():
    """scan validation, OSD evaluation, and the prune rule all derive
    from expr.CMP_TABLE; a half-defined comparator cannot exist."""
    assert ex.COMPARATORS == tuple(ex.CMP_TABLE)
    with pytest.raises(TypeError):
        ex.Comparator(np.less)  # no prune rule: unregisterable
    with pytest.raises(ValueError):
        ex.Cmp("y", "~", 1)  # unknown comparator refused at construction
    from repro.core import Scan
    with pytest.raises(ValueError):
        Scan(dataset="t").filter("y", "~", 1)
    table = {"y": np.arange(10)}
    for cmp in ex.COMPARATORS:
        leaf = ex.Cmp("y", cmp, 5)
        mask = leaf.mask(table)
        assert mask.dtype == np.bool_ and mask.shape == (10,)
        # every registered comparator has a (sound) prune answer — no
        # silent never-prune for operators outside a hand-written chain
        assert isinstance(leaf.prunes({"y": [0, 4]}), bool)
    assert ex.Cmp("y", "!=", 5).prunes({"y": [5, 5]})
    assert not ex.Cmp("y", "!=", 5).prunes({"y": [4, 5]})


def test_expression_wire_form_roundtrips_and_is_json():
    tree = ex.Or((
        ex.And((ex.Cmp("a", "<", 3), ex.In("b", (1, 2, np.int32(3))))),
        ex.Not(ex.Between("a", 0, 9)),
        ex.StrPrefix("s", "pre")))
    wire = tree.to_json()
    json.dumps(wire)  # numpy scalars normalized: actually serializable
    back = ex.from_json(wire)
    assert back.columns() == tree.columns() == frozenset({"a", "b", "s"})
    zm = {"a": [5, 6], "b": [9, 9], "s": ["zzz", "zzz"]}
    assert back.prunes(zm) == tree.prunes(zm)
    with pytest.raises(ValueError):
        ex.from_json({"t": "nope"})
    with pytest.raises(ValueError):
        ex.And(())
    with pytest.raises(TypeError):
        ex.ensure(42)


def test_builder_expression_validation():
    from repro.core import Scan
    s = Scan(dataset="t")
    with pytest.raises(ValueError):
        s.or_(("y", "<", 1))  # one alternative is not an OR
    two = s.or_(("y", "<", 1), ex.Cmp("y", ">", 9))
    assert isinstance(two.predicate, ex.Or)
    chained = two.filter("x", ">", 0.0).isin("y", [1, 2])
    assert isinstance(chained.predicate, ex.And)
    assert len(chained.predicate.children) == 3  # flat conjunction


# ------------------------------------------------- value expressions
_XY = {"x": np.array([1.5, -2.0, 4.0]), "y": np.array([2, 3, 5], np.int32)}


@pytest.mark.parametrize("tree,want,text,cols", [
    (ex.Col("x"), [1.5, -2.0, 4.0], "x", {"x"}),
    (ex.Lit(2), [2.0, 2.0, 2.0], "2.0", set()),
    (ex.Col("x") * ex.Col("y"), [3.0, -6.0, 20.0], "x * y", {"x", "y"}),
    (ex.Col("x") + 1, [2.5, -1.0, 5.0], "x + 1.0", {"x"}),
    (1 - ex.Col("x"), [-0.5, 3.0, -3.0], "1.0 - x", {"x"}),
    (ex.Col("x") * (1 - ex.Col("y")) * (1 + ex.Col("y")),
     [-4.5, 16.0, -96.0], "(x * (1.0 - y)) * (1.0 + y)", {"x", "y"}),
], ids=["col", "lit", "mul", "add", "rsub", "nested"])
def test_value_expression_evaluates_names_and_roundtrips(tree, want, text,
                                                         cols):
    got = tree.evaluate(_XY)
    assert got.dtype == np.float64 and got.shape == (3,)
    np.testing.assert_array_equal(got, want)
    assert tree.text() == text
    assert tree.columns() == frozenset(cols)
    back = ex.value_from_json(json.loads(json.dumps(tree.to_json())))
    assert back == tree and back.text() == text
    assert ex.ensure_value(tree.to_json()) == tree


def test_value_expression_validation():
    assert ex.ensure_value(np.int32(3)) == ex.Lit(3.0)
    with pytest.raises(ValueError):
        ex.Arith("/", ex.Col("x"), ex.Col("y"))
    with pytest.raises(TypeError):
        ex.Arith("*", ex.Col("x"), "y")
    for bad in (True, "x"):
        with pytest.raises(TypeError):
            ex.ensure_value(bad)
    with pytest.raises(ValueError):
        ex.value_from_json({"t": "cmp", "col": "x", "cmp": "<", "value": 1})


# ------------------------------------------------- soundness property
_cols = ("a", "b")
_val = st.integers(-20, 20)
_leaf = st.one_of(
    st.tuples(st.sampled_from(_cols), st.sampled_from(ex.COMPARATORS),
              _val).map(lambda t: ex.Cmp(*t)),
    st.tuples(st.sampled_from(_cols),
              st.lists(_val, max_size=4)).map(
                  lambda t: ex.In(t[0], tuple(t[1]))),
    st.tuples(st.sampled_from(_cols), _val, _val).map(
        lambda t: ex.Between(t[0], min(t[1], t[2]), max(t[1], t[2]))))
_tree = st.recursive(_leaf, lambda ch: st.one_of(
    st.lists(ch, min_size=1, max_size=3).map(lambda l: ex.And(tuple(l))),
    st.lists(ch, min_size=1, max_size=3).map(lambda l: ex.Or(tuple(l))),
    ch.map(ex.Not)), max_leaves=10)
_zone = st.tuples(_val, _val).map(lambda t: [min(t), max(t)])
_zms = st.fixed_dictionaries({"a": _zone, "b": _zone})


@settings(max_examples=200, deadline=None)
@given(_zms, _tree)
def test_prune_soundness_and_strategy_parity(zm, tree):
    """For random zone maps and random expression trees: (1) the wire
    form is lossless, (2) pruned implies ZERO matching rows for any
    table whose values respect the zone bounds (soundness), and (3) the
    client planner's decision equals the OSD's on identical metadata —
    they are literally the same rule."""
    table = {k: np.concatenate(
        [np.array([lo, hi], dtype=np.float64), np.linspace(lo, hi, 9)])
        for k, (lo, hi) in zm.items()}
    wire = tree.to_json()
    assert ex.from_json(wire) == tree
    if oc.zone_map_prunes(zm, tree):         # client planner's call
        assert not tree.mask(table).any()    # ...must be sound
    osd = OSD("osd.prop")
    osd.xattrs["o"] = {"zone_map": zm}
    assert osd._prunes_locally("o", ex.ensure_pred(wire)) \
        == oc.zone_map_prunes(zm, tree)
