"""Batched scatter-gather data plane (exec_batch + column pruning +
vectorized bitpack codec).  Example-based on purpose: this module must
run even when hypothesis is unavailable."""

import numpy as np
import pytest

from repro.core import (Column, GlobalVOL, LogicalDataset, PartitionPolicy,
                        Query, RowRange, SkyhookDriver, make_store)
from repro.core import expr as ex
from repro.core import format as fmt
from repro.core import objclass as oc
from repro.core.scan import Scan
from repro.core.store import PER_REQUEST_OVERHEAD_BYTES


def make_world(n=4000, n_osds=5, replicas=3, seed=0):
    rng = np.random.default_rng(seed)
    ds = LogicalDataset(
        "t", (Column("x", "float64"), Column("y", "int32"),
              Column("z", "float32")), n, 64)
    store = make_store(n_osds, replicas=replicas)
    vol = GlobalVOL(store)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=8 << 10,
                                          max_object_bytes=8 << 12))
    table = {"x": rng.normal(size=n),
             "y": rng.integers(0, 1000, n).astype(np.int32),
             "z": rng.normal(size=n).astype(np.float32)}
    vol.write(omap, table)
    return store, vol, omap, table


FILTER_AGG = [oc.op("filter", col="y", cmp="<", value=500),
              oc.op("agg", col="x", fn="sum")]


# ------------------------------------------------------------- exec_batch
def test_exec_batch_results_match_per_object_exec():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    batch = store.exec_batch(names, FILTER_AGG)
    single = [store.exec(n, FILTER_AGG) for n in names]
    assert len(batch) == len(single)
    for b, s in zip(batch, single):
        assert set(b) == set(s)
        for k in b:
            assert np.array_equal(b[k], s[k]), k


def test_exec_batch_one_request_per_osd_and_same_bytes():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    primaries = {store.cluster.primary(n) for n in names}

    store.fabric.reset()
    store.exec_batch(names, FILTER_AGG)
    batched = store.fabric.snapshot()

    store.fabric.reset()
    for n in names:
        store.exec(n, FILTER_AGG)
    per_obj = store.fabric.snapshot()

    # ops collapse from N to the number of primaries (<= K OSDs)
    assert per_obj["ops"] == len(names)
    assert batched["ops"] == len(primaries)
    assert batched["ops"] <= len(store.cluster.up_osds)
    assert batched["overhead_bytes"] == \
        batched["ops"] * PER_REQUEST_OVERHEAD_BYTES
    # payload accounting is identical: same results, same scanned bytes
    assert batched["client_rx"] == per_obj["client_rx"]
    assert batched["local_bytes"] == per_obj["local_bytes"]


def test_exec_batch_per_object_pipelines():
    store, vol, omap, table = make_world()
    names = omap.object_names()[:3]
    pipelines = [[oc.op("select", rows=(0, k + 1))] for k in range(3)]
    blobs = store.exec_batch(names, pipelines)
    for k, blob in enumerate(blobs):
        assert fmt.block_header(blob)["n_rows"] == k + 1
    with pytest.raises(ValueError):
        store.exec_batch(names, pipelines[:2])


def test_exec_batch_failover_to_replica_mid_batch():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    expect = store.exec_batch(names, FILTER_AGG)

    # primary silently lost one object: that item must fail over to a
    # replica inside the batch while everything else stays batched
    victim = names[0]
    primary = store.cluster.primary(victim)
    with store.osds[primary].lock:
        del store.osds[primary].data[victim]
    store.fabric.reset()
    got = store.exec_batch(names, FILTER_AGG)
    for g, e in zip(got, expect):
        for k in e:
            assert np.array_equal(g[k], e[k])
    primaries = {store.cluster.primary(n) for n in names}
    assert store.fabric.snapshot()["ops"] == len(primaries) + 1  # + retry


def test_exec_batch_failover_on_osd_failure():
    store, vol, omap, table = make_world()
    names = omap.object_names()
    expect = store.exec_batch(names, FILTER_AGG)
    store.fail_osd(store.cluster.primary(names[0]))
    got = store.exec_batch(names, FILTER_AGG)
    for g, e in zip(got, expect):
        for k in e:
            assert np.array_equal(g[k], e[k])


def test_exec_batch_raises_when_all_replicas_lost():
    store, vol, omap, table = make_world()
    name = omap.object_names()[0]
    for osd in store.osds.values():
        with osd.lock:
            osd.data.pop(name, None)
    with pytest.raises(KeyError):
        store.exec_batch([name], FILTER_AGG)


def test_query_ops_bounded_by_osds_not_objects():
    store, vol, omap, table = make_world()
    assert omap.n_objects > len(store.cluster.up_osds)
    res, stats = vol.query(omap, FILTER_AGG)
    assert stats["ops"] <= len(store.cluster.up_osds)
    assert res == pytest.approx(
        table["x"][table["y"] < 500].sum(), rel=1e-12)


def test_driver_query_ops_bounded_and_correct():
    store, vol, omap, table = make_world()
    drv = SkyhookDriver(vol, n_workers=3)
    q = Query("t", filter=("y", "<", 500), aggregate=("mean", "x"))
    r, s = drv.execute(q)
    assert r == pytest.approx(table["x"][table["y"] < 500].mean(),
                              rel=1e-12)
    assert s.fabric_ops <= len(store.cluster.up_osds)


def test_read_through_batch_equals_slice():
    store, vol, omap, table = make_world()
    store.fabric.reset()
    out = vol.read(omap, RowRange(100, 1300), columns=["y", "z"])
    assert np.array_equal(out["y"], table["y"][100:1300])
    assert np.allclose(out["z"], table["z"][100:1300])
    assert store.fabric.ops <= len(store.cluster.up_osds)


# ------------------------------------------------------- zone-map cache
# (the client-side prune plane: pinned to prune="client" — the default
# pushed-down prune needs no client zone-map cache at all)
def test_zone_map_cache_amortizes_xattr_lookups():
    store, vol, omap, table = make_world()
    store.fabric.reset()
    vol.query(omap, FILTER_AGG, prune="client")
    # the writing client cached its own zone maps on write: no lookups
    assert store.fabric.xattr_ops == 0
    # a fresh client warms its whole cache with ONE batched metadata
    # request per OSD (not one lookup per object, let alone per
    # obj x filter even with two filters in the pipeline), then runs warm
    primaries = {store.cluster.primary(n) for n in omap.object_names()}
    assert len(primaries) < omap.n_objects  # N > K or the claim is vacuous
    vol2 = GlobalVOL(store)
    store.fabric.reset()
    two_filters = [oc.op("filter", col="y", cmp=">", value=0),
                   oc.op("filter", col="y", cmp="<", value=900),
                   oc.op("agg", col="x", fn="count")]
    vol2.query(omap, two_filters, prune="client")
    assert store.fabric.xattr_ops == len(primaries)
    vol2.query(omap, two_filters, prune="client")
    assert store.fabric.xattr_ops == len(primaries)  # warm: no new ones
    # the pushed-down prune path needs NO zone-map requests at all
    vol3 = GlobalVOL(store)
    store.fabric.reset()
    vol3.query(omap, two_filters)
    assert store.fabric.xattr_ops == 0


def test_zone_map_cache_invalidated_on_epoch_bump():
    store, vol, omap, table = make_world()
    vol.query(omap, FILTER_AGG, prune="client")
    store.fail_osd(store.cluster.up_osds[0])  # epoch bump
    store.recover()
    store.fabric.reset()
    res, stats = vol.query(omap, FILTER_AGG, prune="client")
    assert store.fabric.xattr_ops > 0  # cache was dropped and re-warmed
    assert res == pytest.approx(table["x"][table["y"] < 500].sum(),
                                rel=1e-12)


def test_zone_map_cache_refreshed_by_write():
    store, vol, omap, table = make_world()
    # warm the cache, then rewrite with shifted data: pruning decisions
    # must follow the NEW zone maps, not the cached ones
    impossible = [oc.op("filter", col="y", cmp=">", value=2000),
                  oc.op("agg", col="x", fn="count")]
    assert vol.query(omap, impossible, prune="client")[0] == 0.0
    table2 = dict(table, y=(table["y"] + 5000).astype(np.int32))
    vol.write(omap, table2)
    res, _ = vol.query(omap, impossible, prune="client")
    assert res == float(len(table2["y"]))


# ------------------------------------------------------- column pruning
def test_required_columns_minimal_sets():
    f = oc.op("filter", col="y", cmp="<", value=1)
    assert oc.required_columns([f, oc.op("agg", col="x", fn="sum")]) == \
        ["x", "y"]
    assert oc.required_columns([oc.op("median", col="x")]) == ["x"]
    assert oc.required_columns(
        [f, oc.op("project", cols=["z"])]) == ["y", "z"]
    # table-out tails without projection keep every column
    assert oc.required_columns([f]) is None
    assert oc.required_columns([oc.op("select", rows=(0, 5))]) is None
    assert oc.required_columns([]) is None
    # non-analyzable ops decode everything
    assert oc.required_columns([oc.op("recompress", codecs={})]) is None
    # an aggregate over a value expression needs every column it reads:
    # TPC-H Q6 decodes its 4 columns of the 16
    q6 = Scan(dataset="lineitem").filter_expr(ex.And((
        ex.Cmp("l_shipdate", ">=", 366), ex.Cmp("l_shipdate", "<", 731),
        ex.Between("l_discount", 0.05, 0.07),
        ex.Cmp("l_quantity", "<", 24)))).agg(
            "sum", ex.Col("l_extendedprice") * ex.Col("l_discount"))
    assert oc.required_columns(q6.pipeline()) == [
        "l_discount", "l_extendedprice", "l_quantity", "l_shipdate"]


def test_pruned_pipeline_equals_full_decode():
    rng = np.random.default_rng(3)
    table = {"a": rng.integers(0, 100, 500).astype(np.int32),
             "b": rng.normal(size=500),
             "c": rng.normal(size=(500, 4)).astype(np.float32)}
    blob = fmt.encode_block(table, codecs={"a": "bitpack7"})
    ops = [oc.op("filter", col="a", cmp=">=", value=50),
           oc.op("agg", col="b", fn="mean")]
    got = oc.run_pipeline(blob, ops)
    full = fmt.decode_block(blob)
    mask = full["a"] >= 50
    assert float(got["sum"]) == pytest.approx(
        full["b"][mask].sum(), rel=1e-15)
    assert float(got["count"]) == float(mask.sum())
    # projection after a filter decodes only the union of their columns
    tab_blob = oc.run_pipeline(blob, [
        oc.op("filter", col="a", cmp=">=", value=50),
        oc.op("project", cols=["b"])])
    out = fmt.decode_block(tab_blob)
    assert set(out) == {"b"}
    assert np.array_equal(out["b"], full["b"][mask])


def test_filter_agg_query_end_to_end_unchanged_by_pruning():
    store, vol, omap, table = make_world()
    for fn in ("sum", "count", "min", "max", "mean"):
        res, _ = vol.query(omap, [
            oc.op("filter", col="y", cmp=">=", value=250),
            oc.op("agg", col="x", fn=fn)])
        sel = table["x"][table["y"] >= 250]
        expect = {"sum": sel.sum(), "count": float(sel.size),
                  "min": sel.min(), "max": sel.max(),
                  "mean": sel.mean()}[fn]
        assert res == pytest.approx(expect, rel=1e-12)


# --------------------------------------------------- vectorized bitpack
def _seed_bitpack_encode(values, bits):
    """The historical per-bit-loop encoder (the bit-exactness oracle)."""
    v = np.ascontiguousarray(values, dtype=np.uint32).ravel()
    n = v.size
    n_groups = -(-n // 32) if n else 0
    padded = np.zeros((n_groups * 32,), np.uint32)
    padded[:n] = v
    g = padded.reshape(n_groups, 32)
    lane = np.arange(32, dtype=np.uint32)
    out = np.zeros((n_groups, bits), np.uint32)
    for k in range(bits):
        out[:, k] = (((g >> np.uint32(k)) & np.uint32(1)) << lane).sum(
            axis=1, dtype=np.uint32)
    return out


@pytest.mark.parametrize("bits", list(range(1, 25)))
def test_bitpack_vectorized_bit_exact_vs_seed(bits):
    rng = np.random.default_rng(bits)
    for n in (0, 1, 31, 32, 33, 100, 1000, 4097):
        v = rng.integers(0, 1 << bits, n).astype(np.uint32)
        words = fmt.bitpack_encode(v, bits)
        assert words.shape == ((-(-n // 32) if n else 0), bits)
        assert np.array_equal(words, _seed_bitpack_encode(v, bits))
        assert np.array_equal(fmt.bitpack_decode(words, bits, n), v)


def test_bitpack_codec_in_block_roundtrip():
    rng = np.random.default_rng(9)
    a = rng.integers(0, 1 << 13, 999).astype(np.int32)
    blob = fmt.encode_block({"a": a}, codecs={"a": "bitpack13"})
    assert np.array_equal(fmt.decode_block(blob)["a"], a)


def test_bit_transpose_does_not_mutate_input():
    # (1, 32) inputs alias their own transpose; the butterfly must work
    # on a private buffer
    v = np.arange(32, dtype=np.uint32)
    before = v.copy()
    fmt.bitpack_encode(v, 6)
    assert np.array_equal(v, before)
    w = fmt.bitpack_encode(v, 6)
    w_before = w.copy()
    fmt.bitpack_decode(w, 6, 32)
    assert np.array_equal(w, w_before)


def test_codec_none_decode_is_zero_copy():
    table = {"x": np.arange(64, dtype=np.int64)}
    blob = fmt.encode_block(table)
    out = fmt.decode_block(blob)
    assert not out["x"].flags.writeable          # aliases the block bytes
    assert not out["x"].flags.owndata
    assert np.array_equal(out["x"], table["x"])


# ------------------------------------------------------------ get_hedged
def test_get_hedged_accounts_transfer_and_uses_shared_pool():
    store, vol, omap, table = make_world(n_osds=4, replicas=2)
    name = omap.object_names()[0]
    primary = store.cluster.primary(name)
    store.osds[primary].latency_s = 0.5
    store.fabric.reset()
    blob = store.get_hedged(name, timeout_s=0.02)
    assert blob == store.osds[store.cluster.locate(name)[1]].get(name)
    snap = store.fabric.snapshot()
    assert snap["client_rx"] == len(blob)     # transfer is accounted now
    assert snap["ops"] == 2                   # hedge + winning replica
    assert snap["overhead_bytes"] == 2 * PER_REQUEST_OVERHEAD_BYTES
    store.osds[primary].latency_s = 0.0


def test_get_hedged_falls_back_past_missing_replica():
    store, vol, omap, table = make_world(n_osds=5, replicas=3)
    name = omap.object_names()[0]
    acting = store.cluster.locate(name)
    # slow primary AND first replica missing the object: the hedge must
    # keep walking the acting set instead of raising
    store.osds[acting[0]].latency_s = 0.5
    with store.osds[acting[1]].lock:
        del store.osds[acting[1]].data[name]
    blob = store.get_hedged(name, timeout_s=0.02)
    assert blob == store.osds[acting[2]].get(name)
    # every replica gone: wait out the slow primary rather than fail
    with store.osds[acting[2]].lock:
        del store.osds[acting[2]].data[name]
    blob2 = store.get_hedged(name, timeout_s=0.02)
    assert blob2 == blob
    store.osds[acting[0]].latency_s = 0.0


def test_data_loader_batches_fetches_per_osd():
    from repro.data.corpus import CorpusSpec, build_corpus
    from repro.data.pipeline import ObjectDataLoader
    store = make_store(6, replicas=2)
    vol = GlobalVOL(store)
    spec = CorpusSpec(n_seqs=256, seq_len=64, vocab_size=5000, seed=1)
    build_corpus(vol, spec, policy=PartitionPolicy(
        target_object_bytes=4 << 10, max_object_bytes=1 << 20))
    loader = ObjectDataLoader(vol, "corpus", global_batch=64, prefetch=0)
    store.fabric.reset()
    batch = loader.make_batch(0)
    assert batch["tokens"].shape == (64, 64)
    # one batched request per OSD, not one per contiguous run
    assert store.fabric.ops <= len(store.cluster.up_osds)
