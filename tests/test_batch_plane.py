"""Symmetric per-OSD batch plane: batched writes (put_batch), server-
side per-OSD combine (exec_combine_iter), batched zone-map metadata
(list_zone_maps), and the cross-client version-tag coherence protocol.
Example-based on purpose: must run without hypothesis."""

import numpy as np
import pytest

from repro.core import (Column, GlobalVOL, LogicalDataset, PartitionPolicy,
                        make_store)
from repro.core import expr as ex
from repro.core import format as fmt
from repro.core import objclass as oc
from repro.core.scan import _by_position
from repro.core.store import OSDDown, PER_REQUEST_OVERHEAD_BYTES


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
    return store, vol, omap, table


def batch_results(store, names, ops):
    """The batch plane's results placed at their input positions."""
    return _by_position(len(names), store.exec_batch_iter(names, ops))


# -------------------------------------------------------------- put_batch
def test_put_batch_one_request_per_osd_and_same_bytes():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    blobs = [f"blob-{i}".encode() * 50 for i in range(len(names))]
    primaries = {store.cluster.primary(n) for n in names}

    store.fabric.reset()
    store.put_batch(names, blobs)
    batched = store.fabric.snapshot()

    store.delete(names[0])  # any state; rewrite per-object for comparison
    store.fabric.reset()
    for n, b in zip(names, blobs):
        store.put(n, b)
    per_obj = store.fabric.snapshot()

    assert per_obj["ops"] == len(names)
    assert batched["ops"] == len(primaries)
    assert batched["ops"] <= len(store.cluster.up_osds)
    assert batched["overhead_bytes"] == \
        batched["ops"] * PER_REQUEST_OVERHEAD_BYTES
    # payload accounting identical: same client bytes, same replication
    assert batched["client_tx"] == per_obj["client_tx"]
    assert batched["replica_bytes"] == per_obj["replica_bytes"]
    # every replica holds every object
    for n, b in zip(names, blobs):
        for osd_id in store.cluster.locate(n):
            assert store.osds[osd_id].data[n] == b


def test_put_batch_stamps_monotonic_versions():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    v1 = store.put_batch(names, [b"a"] * len(names))
    v2 = store.put_batch(names, [b"b"] * len(names))
    assert len(v1) == len(names) and len(set(v1)) == len(names)
    assert min(v2) > max(v1)  # strictly monotonic across writes
    for n, v in zip(names, v2):
        assert store.xattr(n)["version"] == v


def test_put_batch_replica_failover_mid_batch():
    """An entry OSD dies mid-batch (its batched request raises): those
    sub-writes must regroup onto the next replica and land, while the
    other groups stay batched."""
    store, vol, omap, table = make_world()
    names = omap.object_names()
    blobs = [f"v-{i}".encode() * 20 for i in range(len(names))]
    primaries = {store.cluster.primary(n) for n in names}
    victim = store.cluster.primary(names[0])

    calls = {"n": 0}
    real = store.osds[victim].put_batch

    def flaky(items, **kw):
        if calls["n"] == 0:  # dies on the first batched request only
            calls["n"] += 1
            raise OSDDown(victim)
        return real(items, **kw)

    store.osds[victim].put_batch = flaky
    store.fabric.reset()
    versions = store.put_batch(names, blobs)
    # one request per primary + one retry round for the victim's group
    assert store.fabric.ops == len(primaries) + 1
    assert len(versions) == len(names)
    # every object is fully replicated with the right content, including
    # on the victim (the retry's server-side fan-out wrote it back)
    for n, b in zip(names, blobs):
        for osd_id in store.cluster.locate(n):
            assert store.osds[osd_id].data[n] == b


def test_put_batch_failover_on_failed_osd():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    store.fail_osd(store.cluster.primary(names[0]))
    store.put_batch(names, [b"x" * 64] * len(names))
    for n in names:
        assert store.get(n) == b"x" * 64


def test_put_batch_partial_land_then_die_keeps_landed_accounting():
    """The entry OSD lands part of its batch then dies: the landed
    sub-writes keep their success (their replica fan-out is already in
    flight) and only the unlanded remainder fails over, so payload
    accounting stays exact — each object's bytes cross the NIC once and
    are replicated exactly (replicas - 1) times."""
    store, vol, omap, table = make_world()
    names = omap.object_names()
    blobs = [f"w-{i}".encode() * 25 for i in range(len(names))]
    by_primary = {}
    for n in names:
        by_primary.setdefault(store.cluster.primary(n), []).append(n)
    victim, group = max(by_primary.items(), key=lambda kv: len(kv[1]))
    assert len(group) >= 2  # need landed AND unlanded sub-writes

    real = store.osds[victim].put_batch
    died = {"yet": False}

    def dies_midway(items, stream=None, landed=None):
        if died["yet"]:
            return real(items, stream=stream, landed=landed)
        died["yet"] = True
        real(items[:1], stream=stream, landed=landed)  # first one lands
        raise OSDDown(victim)

    store.osds[victim].put_batch = dies_midway
    store.fabric.reset()
    store.put_batch(names, blobs)
    for n, b in zip(names, blobs):
        for osd_id in store.cluster.locate(n):
            assert store.osds[osd_id].data[n] == b
    payload = sum(len(b) for b in blobs)
    assert store.fabric.client_tx == payload
    assert store.fabric.replica_bytes == \
        payload * (store.cluster.replicas - 1)


def test_put_batch_length_mismatch_raises():
    store, vol, omap, table = make_world()
    with pytest.raises(ValueError):
        store.put_batch(["a", "b"], [b"1"])


def test_vol_write_ingest_costs_one_request_per_osd():
    store, vol, omap, table = make_world()
    primaries = {store.cluster.primary(n) for n in omap.object_names()}
    assert omap.n_objects > len(primaries)  # N > K or the claim is vacuous
    store.fabric.reset()
    vol.write(omap, table)
    assert store.fabric.ops == len(primaries)
    # and the data reads back exactly
    from repro.core import RowRange
    out = vol.read(omap, RowRange(0, omap.dataset.n_rows))
    assert np.allclose(out["x"], table["x"])
    assert np.array_equal(out["y"], table["y"])


# ------------------------------------------------------- per-OSD combine
ALL_TAILS = [("agg", fn) for fn in ("sum", "count", "min", "max", "mean")]
# the same tails over a value expression, as ``Scan.agg`` ships it
X_TIMES_Y = (ex.Col("x") * (1 - ex.Col("y"))).to_json()
COMBINE_CASES = [(t, fn, "x") for t, fn in ALL_TAILS] + \
    [(t, fn, X_TIMES_Y) for t, fn in ALL_TAILS]


@pytest.mark.parametrize(
    "tail,fn,col", COMBINE_CASES,
    ids=[f"{t}-{fn}" + ("" if c == "x" else "-expr")
         for t, fn, c in COMBINE_CASES])
def test_exec_combine_equals_client_side_combine(tail, fn, col):
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    names = omap.object_names()
    ops = [oc.op("filter", col="y", cmp="<", value=500),
           oc.op(tail, col=col, fn=fn)]
    per_object = batch_results(store, names, ops)
    merged = list(store.exec_combine_iter(names, ops))
    # one partial per OSD, not per object
    primaries = {store.cluster.primary(n) for n in names}
    assert len(merged) <= len(primaries) < len(per_object)
    assert oc.combine_partials(ops, merged) == pytest.approx(
        oc.combine_partials(ops, per_object), rel=1e-12)
    # and both agree with numpy over the rows that pass
    v = table["x"] if col == "x" else table["x"] * (1.0 - table["y"])
    v = v[table["y"] < 500]
    want = {"sum": v.sum(), "count": v.size, "min": v.min(),
            "max": v.max(), "mean": v.mean()}[fn]
    assert oc.combine_partials(ops, merged) == pytest.approx(want,
                                                             rel=1e-12)


def test_exec_combine_quantile_sketch_tail():
    store, vol, omap, table = make_world(n=30_000)
    vol.write(omap, table)
    names = omap.object_names()
    ops = [oc.op("quantile_sketch", col="x", lo=-6.0, hi=6.0)]
    merged = list(store.exec_combine_iter(names, ops))
    per_object = batch_results(store, names, ops)
    assert oc.combine_partials(ops, merged) == pytest.approx(
        oc.combine_partials(ops, per_object), rel=1e-12)


def test_exec_combine_client_rx_is_o_k():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    names = omap.object_names()
    ops = [oc.op("agg", col="x", fn="mean")]
    primaries = {store.cluster.primary(n) for n in names}

    store.fabric.reset()
    list(store.exec_combine_iter(names, ops))
    combined = store.fabric.snapshot()
    store.fabric.reset()
    batch_results(store, names, ops)
    batched = store.fabric.snapshot()

    assert combined["ops"] == batched["ops"] == len(primaries)
    # rx shrinks from one partial per OBJECT to one per OSD; same scan
    assert combined["client_rx"] == len(primaries) * 16  # {sum,count} f64
    assert batched["client_rx"] == len(names) * 16
    assert combined["local_bytes"] == batched["local_bytes"]


def test_exec_combine_failover_to_replica_mid_batch():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    names = omap.object_names()
    ops = [oc.op("agg", col="x", fn="sum")]
    expect = oc.combine_partials(
        ops, list(store.exec_combine_iter(names, ops)))
    # primary silently lost one object: its partial must come from a
    # replica (as a second, batched, request) and the total must match
    victim = names[0]
    primary = store.cluster.primary(victim)
    with store.osds[primary].lock:
        del store.osds[primary].data[victim]
    store.fabric.reset()
    merged = list(store.exec_combine_iter(names, ops))
    primaries = {store.cluster.primary(n) for n in names}
    assert store.fabric.ops == len(primaries) + 1  # + one retry request
    assert oc.combine_partials(ops, merged) == pytest.approx(expect,
                                                             rel=1e-12)


def test_exec_combine_raises_when_all_replicas_lost():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    name = omap.object_names()[0]
    for osd in store.osds.values():
        with osd.lock:
            osd.data.pop(name, None)
    with pytest.raises(KeyError):
        list(store.exec_combine_iter([name],
                                     [oc.op("agg", col="x", fn="sum")]))


def test_exec_combine_rejects_non_mergeable_pipeline():
    store, vol, omap, table = make_world()
    with pytest.raises(ValueError):
        list(store.exec_combine_iter(omap.object_names(),
                                     [oc.op("median", col="x")]))


def test_query_and_driver_use_per_osd_combine():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    primaries = {store.cluster.primary(n) for n in omap.object_names()}
    res, stats = vol.query(omap, [oc.op("agg", col="x", fn="sum")])
    assert res == pytest.approx(table["x"].sum(), rel=1e-12)
    assert stats["ops"] <= len(primaries)
    assert stats["client_rx"] <= len(primaries) * 16

    r, s = vol.scan("t").filter("y", "<", 500).agg("mean", "x").execute()
    assert r == pytest.approx(table["x"][table["y"] < 500].mean(),
                              rel=1e-12)
    assert s["ops"] <= len(primaries)
    assert s["client_rx"] <= len(primaries) * 16


# --------------------------------------------------- zone-map metadata
def test_list_zone_maps_batches_and_fails_over():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    names = omap.object_names()
    primaries = {store.cluster.primary(n) for n in names}

    store.fabric.reset()
    infos = store.list_zone_maps(names)
    assert store.fabric.xattr_ops == len(primaries)  # one per OSD, not N
    assert set(infos) == set(names)
    for n in names:
        assert infos[n]["zone_map"] == store.xattr(n)["zone_map"]
        assert infos[n]["version"] == store.xattr(n)["version"]

    # primary lost one object's xattr: the listing fails over
    victim = names[0]
    primary = store.cluster.primary(victim)
    with store.osds[primary].lock:
        del store.osds[primary].xattrs[victim]
    store.fabric.reset()
    infos = store.list_zone_maps(names)
    assert set(infos) == set(names)
    assert store.fabric.xattr_ops == len(primaries) + 1  # + retry request

    # an object with no xattr anywhere is simply absent
    assert "nowhere" not in store.list_zone_maps(["nowhere"])


def test_plan_warms_cache_in_k_requests():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    primaries = {store.cluster.primary(n) for n in omap.object_names()}
    fresh = GlobalVOL(store)
    store.fabric.reset()
    fresh.plan(omap, [oc.op("filter", col="y", cmp="<", value=500),
                      oc.op("agg", col="x", fn="sum")])
    assert store.fabric.xattr_ops <= len(primaries)
    assert store.fabric.xattr_ops < omap.n_objects


# --------------------------------------------- cross-client coherence
# (the client-side prune plane: pinned to prune="client" — under the
# default pushed-down prune the OSD always sees its own CURRENT zone
# maps, so there is no cache to go stale; tests/test_scan.py covers
# that side of the symmetry)
def test_two_client_stale_zone_map_caught_by_version_tag():
    """Client A warms its zone-map cache; client B rewrites the data at
    the SAME cluster epoch.  A's next plan must revalidate its
    prune-positive objects against the bumped version tags and un-prune
    the rewritten objects — the stale-prune hazard PR 1 documented."""
    store, vol_a, omap, table = make_world()
    vol_b = GlobalVOL(store)
    vol_a.write(omap, table)

    impossible = [oc.op("filter", col="y", cmp=">", value=2000),
                  oc.op("agg", col="x", fn="count")]
    res, stats = vol_a.query(omap, impossible, prune="client")
    assert res == 0.0 and stats["objects_pruned"] == omap.n_objects

    # client B (same epoch!) rewrites with values that DO match
    assert store.cluster.epoch == 0
    table2 = dict(table, y=(table["y"] + 5000).astype(np.int32))
    vol_b.write(omap, table2)
    assert store.cluster.epoch == 0  # no epoch bump to hide behind

    res2, stats2 = vol_a.query(omap, impossible, prune="client")
    assert res2 == float(len(table2["y"]))  # stale prune would say 0
    assert stats2["objects_pruned"] == 0


def test_revalidated_unprune_preserves_row_order():
    """A revalidation un-prune must slot the object back at its row
    position, not append it — table-out gathers concat in plan order."""
    store, vol_a, omap, table = make_world()
    vol_b = GlobalVOL(store)
    vol_a.write(omap, table)
    # make object 0 (rows at the FRONT) prune-positive for client A
    flt = [oc.op("filter", col="y", cmp="<", value=20_000)]
    first = omap.extents[0]
    low = dict(table)
    low["y"] = table["y"].copy()
    low["y"][first.row_start:first.row_stop] = 50_000  # prunes under flt
    vol_a.write(omap, low)
    plan_a = vol_a.plan(omap, flt)
    assert plan_a.pruned == (first.name,)
    # client B rewrites everything back so nothing should prune
    vol_b.write(omap, table)
    out, _ = vol_a.query(omap, flt, prune="client")  # table-out pipeline
    assert np.array_equal(out["y"], table["y"])  # rows in ROW order


def test_version_revalidation_costs_only_k_requests():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    primaries = {store.cluster.primary(n) for n in omap.object_names()}
    impossible = [oc.op("filter", col="y", cmp=">", value=2000),
                  oc.op("agg", col="x", fn="count")]
    vol.query(omap, impossible, prune="client")  # warm; everything prunes
    store.fabric.reset()
    vol.query(omap, impossible, prune="client")
    # the repeat query pays ONLY the prune revalidation: <= K metadata
    # requests, zero data requests (everything still prunes)
    assert store.fabric.xattr_ops <= len(primaries)
    assert store.fabric.ops == 0


def test_unpruned_scan_needs_no_revalidation():
    store, vol, omap, table = make_world()
    vol.write(omap, table)
    nothing_prunes = [oc.op("filter", col="y", cmp="<", value=2000),
                      oc.op("agg", col="x", fn="count")]
    vol.query(omap, nothing_prunes, prune="client")
    store.fabric.reset()
    vol.query(omap, nothing_prunes, prune="client")
    assert store.fabric.xattr_ops == 0  # kept objects revalidate for free


# --------------------------------------------- consumers of put_batch
def test_checkpoint_save_writes_in_k_requests_per_leaf():
    from repro.checkpoint import ckpt
    store = make_store(4, replicas=2)
    state = {"w": np.arange(4096, dtype=np.float32),
             "b": np.ones(128, dtype=np.float32)}
    store.fabric.reset()
    ckpt.save(store, state, step=10,
              policy=PartitionPolicy(target_object_bytes=2 << 10,
                                     max_object_bytes=2 << 10))
    # each leaf's objects ride one batched request per OSD (2 leaves
    # here) + 1 manifest put — not one request per object
    n_objects = len(store.list_objects("ckpt/")) - 1
    k = len(store.cluster.up_osds)
    assert store.fabric.ops <= 2 * k + 1
    assert n_objects > k  # the claim is non-vacuous
    restored, _ = ckpt.restore(store, state, step=10)
    assert np.array_equal(restored["w"], state["w"])
    assert np.array_equal(restored["b"], state["b"])


# --------------------------------------------- device bitunpack routing
def test_device_bitunpack_bit_exact_vs_numpy():
    jax = pytest.importorskip("jax")
    del jax
    from repro.kernels.bitunpack import bitunpack_columns
    rng = np.random.default_rng(7)
    for bits in (1, 7, 13, 17):
        for n in (0, 1, 31, 32, 129, 1000, 4096):
            v = rng.integers(0, 1 << bits, n).astype(np.uint32)
            words = fmt.bitpack_encode(v, bits)
            (got,) = bitunpack_columns([(words, bits, n)], interpret=True)
            assert np.array_equal(got, fmt.bitpack_decode(words, bits, n))


def test_run_pipeline_with_device_bitunpack_backend():
    pytest.importorskip("jax")
    rng = np.random.default_rng(11)
    table = {"a": rng.integers(0, 1 << 9, 500).astype(np.int32),
             "b": rng.normal(size=500)}
    blob = fmt.encode_block(table, codecs={"a": "bitpack9"})
    ops = [oc.op("filter", col="a", cmp=">=", value=100),
           oc.op("agg", col="b", fn="sum")]
    expect = oc.run_pipeline(blob, ops)
    fmt.set_bitunpack_backend("device")  # interpret-mode Pallas on CPU
    try:
        got = oc.run_pipeline(blob, ops)
        dec = fmt.decode_block(blob)
    finally:
        fmt.set_bitunpack_backend("auto")
    assert float(got["sum"]) == float(expect["sum"])
    assert np.array_equal(dec["a"], table["a"])


@pytest.mark.parametrize("columns", [None, ["k", "raw", "z", "q"], ["q"],
                                     ["raw", "z"]])
def test_device_decode_block_is_one_round_trip(columns):
    """The device backend decodes every requested bitpacked column of a
    block in one round trip, and equals the numpy backend, whole and
    projected; ``none`` and ``zlib`` columns never reach the device."""
    pytest.importorskip("jax")
    from repro.kernels.bitunpack import decode_stats
    rng = np.random.default_rng(17)
    n = 3000
    table = {"k": rng.integers(0, 1 << 20, n).astype(np.int32),
             "raw": rng.normal(size=n),
             "q": rng.integers(0, 50, n).astype(np.int32),
             "z": rng.integers(-5, 5, n).astype(np.int64),
             "d": rng.integers(0, 2500, n).astype(np.int32)}
    blob = fmt.encode_block(table, codecs={"k": "bitpack20", "q": "bitpack6",
                                           "z": "zlib", "d": "bitpack12"})
    expect = fmt.decode_block(blob, columns)
    fmt.set_bitunpack_backend("device")  # interpret-mode Pallas on CPU
    try:
        before = decode_stats()
        got = fmt.decode_block(blob, columns)
        after = decode_stats()
    finally:
        fmt.set_bitunpack_backend("auto")
    packed = [c for c in (columns or table) if c in ("k", "q", "d")]
    assert after["trips"] - before["trips"] == int(bool(packed))
    assert after["calls"] - before["calls"] == len(packed)
    assert list(got) == list(expect)
    for c in got:
        assert got[c].dtype == table[c].dtype
        assert np.array_equal(got[c], expect[c])
        assert np.array_equal(got[c], table[c])


def test_unpack_tokens_pallas_matches_reference():
    pytest.importorskip("jax")
    from repro.data.fused_ingest import pack_batch, unpack_tokens
    rng = np.random.default_rng(13)
    toks = rng.integers(0, 1 << 11, (4, 128)).astype(np.int32)
    packed = pack_batch(toks, 11)
    ref = np.asarray(unpack_tokens(packed))
    pal = np.asarray(unpack_tokens(packed, use_pallas=True,
                                   interpret=True))
    assert np.array_equal(ref, toks)
    assert np.array_equal(pal, toks)


def test_auto_bitunpack_on_tpu_propagates_kernel_errors(monkeypatch):
    """On a TPU backend "auto" decodes bitpack columns with the kernel
    and a kernel error ends the scan: no warning, no numpy answer."""
    import warnings

    import jax
    from repro.kernels import bitunpack as bu

    def broken_kernel(cols, **_):
        raise RuntimeError("kernel failed to lower")

    store, vol, omap, table = make_world()
    vol.write(omap, table)
    assert {c["codec"] for c in fmt.block_header(
        store.get(omap.extents[0].name))["columns"]} >= {"bitpack10"}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bu, "bitunpack_columns", broken_kernel)
    fmt.set_bitunpack_backend("auto")
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            with pytest.raises(RuntimeError, match="failed to lower"):
                vol.scan("t").filter("y", "<", 500).agg("sum", "x") \
                    .execute()
            with pytest.raises(RuntimeError, match="failed to lower"):
                fmt.decode_block(store.get(omap.extents[0].name))
    finally:
        fmt.set_bitunpack_backend("auto")
