"""Self-healing storage plane: digests, scrub/heal, fault injection,
and the deadline/backoff request layer (paper §2 "failure management").
"""

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.checkpoint import ckpt
from repro.core import (Column, DataLossError, FaultInjector, GlobalVOL,
                        LogicalDataset, PartitionPolicy, RetryPolicy,
                        RowRange, make_store)
from repro.core import format as fmt
from repro.core import objclass as oc
from repro.core.format import content_digest
from repro.core.store import PartialWriteError


def make_world(n=4000, n_osds=6, replicas=3, seed=0, obj_kb=8, **store_kw):
    rng = np.random.default_rng(seed)
    ds = LogicalDataset(
        "t", (Column("x", "float64"), Column("y", "int32")), n, 64)
    store = make_store(n_osds, replicas=replicas, **store_kw)
    vol = GlobalVOL(store)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=obj_kb << 10,
                                          max_object_bytes=obj_kb << 13))
    table = {"x": rng.normal(size=n),
             "y": rng.integers(0, 1000, n).astype(np.int32)}
    vol.write(omap, table)
    return store, vol, omap, table


def _copies_all_verify(store, name):
    for osd_id in store.cluster.locate(name):
        osd = store.osds[osd_id]
        assert name in osd.data, (name, osd_id)
        x = osd.xattrs.get(name) or {}
        assert "digest" in x, (name, osd_id)
        assert content_digest(osd.data[name]) == int(x["digest"])


def _extent_stamps_match(store, name, block):
    """Every copy of ``name`` carries extent digests that match its
    bytes when it is a column-layout block, and none otherwise."""
    for osd_id in store.cluster.locate(name):
        osd = store.osds[osd_id]
        x = osd.xattrs[name]
        if block:
            assert x["extent_digests"] == fmt.stamp_digests(
                osd.data[name])["extent_digests"], (name, osd_id)
        else:
            assert "extent_digests" not in x, (name, osd_id)


def _extents(blob):
    """``{"header": (start, stop), <column>: (start, stop), ...}`` of a
    column-layout block."""
    hlen = int.from_bytes(blob[4:8], "little")
    out, off = {"header": (0, 8 + hlen)}, 8 + hlen
    header = fmt.block_header(blob)
    for c, n in zip(header["columns"], header["lens"]):
        out[c["name"]] = (off, off + n)
        off += n
    return out


def _rot(store, name, osd_id, extent):
    """Flip one bit in the middle of ``extent`` (a column name or
    "header") of the copy of ``name`` on ``osd_id``."""
    osd = store.osds[osd_id]
    with osd.lock:
        blob = bytearray(osd.data[name])
        s, e = _extents(bytes(blob))[extent]
        blob[(s + e) // 2] ^= 0x10
        osd.data[name] = bytes(blob)


# ------------------------------------------------------- digest substrate
def test_digest_stamped_on_every_write_path_and_hop():
    store, vol, omap, table = make_world()
    # vol.write rode put_batch; every replica of every object (each
    # chain hop forwards blob + xattr together) carries a digest
    for name in omap.object_names():
        _copies_all_verify(store, name)
    # the per-object put path stamps too
    store.put("solo", b"some bytes")
    _copies_all_verify(store, "solo")
    # and the windowed streaming path
    names = [f"w/{i}" for i in range(6)]
    blobs = [bytes([i]) * 2048 for i in range(6)]
    store.put_batch(names, ((b, None) for b in blobs), window_bytes=4096)
    for name in names:
        _copies_all_verify(store, name)
    # extent digests: on every copy of every column-layout block, none
    # on other bytes, and none carried over from another blob's xattr
    for name in omap.object_names():
        _extent_stamps_match(store, name, block=True)
    for name in ["solo", *names]:
        _extent_stamps_match(store, name, block=False)
    store.put("solo", b"other bytes", store.xattr(omap.object_names()[0]))
    _copies_all_verify(store, "solo")
    _extent_stamps_match(store, "solo", block=False)
    # the per-object put and the windowed stream stamp blocks too
    blocks = [vol.local.encode({"v": np.arange(64.0) + i}) for i in range(3)]
    store.put("blk", blocks[0])
    store.put_batch(["wb/0", "wb/1"], ((b, None) for b in blocks[1:]),
                    window_bytes=1)
    # and the OSD-side compaction, replicated down its chain
    store.compact_run(omap.object_names()[:2], "cmp")
    for name in ["blk", "wb/0", "wb/1", "cmp"]:
        _copies_all_verify(store, name)
        _extent_stamps_match(store, name, block=True)


def test_corrupt_primary_read_fails_over_and_is_counted():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    name = omap.object_names()[0]
    fi.flip_bits(name, osd_id=store.cluster.locate(name)[0], n_bits=5)
    out = vol.read(omap, RowRange(0, 1000))
    assert np.allclose(out["x"], table["x"][:1000])  # zero wrong bytes
    assert store.fabric.corruptions_detected == 1
    # the bad copy is quarantined on its OSD, out of service
    prim = store.cluster.locate(name)[0]
    assert name in store.osds[prim].quarantine
    assert name not in store.osds[prim].data


def test_all_replicas_corrupt_is_loud_data_loss():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    name = omap.object_names()[0]
    for osd_id in list(store.cluster.locate(name)):
        fi.flip_bits(name, osd_id=osd_id)
    with pytest.raises(DataLossError) as ei:
        store.get(name)
    assert name in ei.value.objects  # the error NAMES the objects


def test_scans_bit_exact_under_replica_corruption():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    # corrupt the PRIMARY copy of several objects: both batched planes
    # (combine for aggregates, concat for table-out) must fail the
    # items over and return bit-exact results
    for name in omap.object_names()[::2]:
        fi.flip_bits(name, osd_id=store.cluster.locate(name)[0])
    r, stats = vol.query(omap, [oc.op("agg", col="y", fn="count")])
    assert r == float(len(table["y"]))
    out = vol.read(omap, RowRange(0, len(table["y"])))
    assert np.array_equal(out["y"], table["y"])
    assert np.allclose(out["x"], table["x"])
    assert store.fabric.corruptions_detected >= len(fi.injected)


# ------------------------------------------- per-extent read verification
def test_projected_read_catches_rot_in_a_column_it_decodes():
    store, vol, omap, table = make_world()
    name = omap.object_names()[0]
    prim = store.cluster.locate(name)[0]
    _rot(store, name, prim, "y")
    r, stats = vol.scan("t").agg("sum", "y").execute(omap)
    assert r == float(table["y"].sum())  # failed over: exact
    assert stats["corruptions_detected"] == 1
    assert name in store.osds[prim].quarantine
    assert name not in store.osds[prim].data


@pytest.mark.parametrize("later", ["full_read", "scrub"])
def test_rot_in_a_column_the_read_skips_waits_for_a_read_or_scrub(later):
    store, vol, omap, table = make_world()
    name = omap.object_names()[0]
    prim = store.cluster.locate(name)[0]
    _rot(store, name, prim, "x")
    r, stats = vol.scan("t").agg("sum", "y").execute(omap)
    assert r == float(table["y"].sum())  # no byte of x was decoded
    assert stats["corruptions_detected"] == 0
    assert name in store.osds[prim].data  # still in service
    if later == "full_read":
        out = vol.read(omap, RowRange(0, len(table["x"])))
        assert np.array_equal(out["x"], table["x"])
        assert store.fabric.corruptions_detected == 1
        assert name in store.osds[prim].quarantine
    stats = store.scrub()
    assert stats["corrupt_copies"] == (1 if later == "scrub" else 0)
    assert stats["healed_copies"] >= 1
    assert store.fabric.corruptions_detected == 1
    _copies_all_verify(store, name)
    _extent_stamps_match(store, name, block=True)


@pytest.mark.parametrize("col", ["x", "y"])
def test_header_rot_is_caught_by_any_projected_read(col):
    store, vol, omap, table = make_world()
    name = omap.object_names()[0]
    prim = store.cluster.locate(name)[0]
    _rot(store, name, prim, "header")
    r, stats = vol.scan("t").agg("sum", col).execute(omap)
    assert r == pytest.approx(float(table[col].sum()), rel=1e-12)
    assert stats["corruptions_detected"] == 1
    assert name in store.osds[prim].quarantine


@pytest.mark.parametrize("read", ["filter_agg", "project"])
def test_row_sliced_projected_read_catches_rot_in_a_decoded_column(read):
    """A row slice resolves on the OSD after the check; the decode still
    reads only the extents the check covered."""
    store, vol, omap, table = make_world()
    name = omap.extents[1].name
    prim = store.cluster.locate(name)[0]
    _rot(store, name, prim, "x")
    blob_bytes = sum(len(store.osds[store.cluster.locate(n)[0]].data[n])
                     for n in omap.object_names())
    store.fabric.reset()
    if read == "filter_agg":
        r, _ = (vol.scan("t").rows(100, 2500).filter("y", "<", 500)
                .agg("sum", "x").execute(omap))
        mask = table["y"][100:2500] < 500
        assert r == pytest.approx(table["x"][100:2500][mask].sum(),
                                  rel=1e-12)
    else:
        out = vol.read(omap, RowRange(100, 2500), columns=["x"])
        assert np.array_equal(out["x"], table["x"][100:2500])
    assert store.fabric.corruptions_detected == 1
    assert name in store.osds[prim].quarantine
    assert 0 < store.fabric.verify_bytes < blob_bytes  # extents only


@pytest.mark.parametrize("copy", ["no_extent_digests", "row_layout"])
def test_copy_without_extent_digests_is_checked_whole(copy):
    store = make_store(4, replicas=2)
    rng = np.random.default_rng(5)
    t = {"x": rng.normal(size=500), "y": rng.integers(0, 99, 500)}
    layout = "row" if copy == "row_layout" else "col"
    blob = fmt.encode_block(t, layout=layout)
    store.put("o", blob)
    acting = store.cluster.locate("o")
    if copy == "no_extent_digests":  # stamped before extent digests
        for osd_id in acting:
            del store.osds[osd_id].xattrs["o"]["extent_digests"]
        _rot(store, "o", acting[0], "x")
    else:
        _extent_stamps_match(store, "o", block=False)
        osd = store.osds[acting[0]]
        with osd.lock:  # rot the last byte: the last row's y
            osd.data["o"] = blob[:-1] + bytes([blob[-1] ^ 1])
    store.fabric.reset()
    ops = [oc.op("agg", col="y", fn="sum")]
    assert oc.combine_partials(
        ops, [r for _, r in store.exec_batch_iter(["o"], ops)]) == \
        t["y"].sum()
    assert store.fabric.corruptions_detected == 1
    # the whole blob on the rotten copy, then on the replica
    assert store.fabric.verify_bytes == 2 * len(blob)


def test_verify_bytes_counts_the_extents_a_read_decodes():
    store, vol, omap, table = make_world()
    blobs = [store.osds[store.cluster.locate(n)[0]].data[n]
             for n in omap.object_names()]
    spans = [_extents(b) for b in blobs]
    store.fabric.reset()
    _, stats = vol.scan("t").agg("sum", "y").execute(omap)
    want = sum(e["header"][1] + e["y"][1] - e["y"][0] for e in spans)
    assert stats["verify_bytes"] == want
    assert want < sum(map(len, blobs))
    store.fabric.reset()
    vol.read(omap, RowRange(0, len(table["x"])))
    assert store.fabric.verify_bytes == sum(map(len, blobs))


# ------------------------------------------------------- retry layer
def test_transient_faults_are_retried_with_backoff():
    store, vol, omap, table = make_world(
        retry=RetryPolicy(attempts=4, base_s=0.0))
    fi = FaultInjector(store)
    victim = store.cluster.primary(omap.object_names()[0])
    fi.transient_failures(victim, 2)  # fail twice, then serve
    r, _ = vol.query(omap, [oc.op("agg", col="y", fn="count")])
    assert r == float(len(table["y"]))
    assert store.fabric.retries >= 2


def test_exhausted_retry_budget_fails_over_to_replica():
    # attempts=1 => no retry at all: the transient is terminal for that
    # replica and the read falls down the acting set
    store, vol, omap, table = make_world(retry=RetryPolicy(attempts=1))
    fi = FaultInjector(store)
    name = omap.object_names()[0]
    fi.transient_failures(store.cluster.locate(name)[0], 50)
    assert store.get(name) is not None
    assert store.fabric.retries == 0


def test_deadline_bounds_retrying():
    p = RetryPolicy(attempts=10, base_s=0.05, cap_s=0.05, deadline_s=0.01)
    # next backoff would cross the deadline immediately
    import time
    assert p.give_up(0, time.perf_counter())
    assert not RetryPolicy(attempts=2).give_up(0, time.perf_counter())
    assert RetryPolicy(attempts=2).give_up(1, time.perf_counter())


def test_slow_osd_degrades_but_stays_correct():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    fi.slow(store.cluster.primary(omap.object_names()[0]), 0.002)
    r, _ = vol.query(omap, [oc.op("agg", col="x", fn="sum")])
    assert r == pytest.approx(table["x"].sum(), rel=1e-12)


# ------------------------------------------------------- scrub / heal
def test_scrub_detects_quarantines_and_heals_bit_rot():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    name = omap.object_names()[0]
    hit = fi.flip_bits(name, n_bits=3)  # maybe a non-primary replica:
    # no read would ever notice — only scrub finds it proactively
    stats = store.scrub()
    assert stats["corrupt_copies"] == 1
    assert stats["healed_copies"] >= 1
    assert store.fabric.corruptions_detected == 1
    assert store.fabric.heals >= 1
    assert store.fabric.scrub_bytes > 0
    assert name in store.osds[hit].quarantine
    _copies_all_verify(store, name)  # healed through the chain path


def test_torn_write_detected_and_healed():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    name = omap.object_names()[1]
    hit = fi.tear_write(name)  # blob landed, xattr (digest) missing
    stats = store.scrub()
    assert stats["corrupt_copies"] == 1
    assert name in store.osds[hit].quarantine
    _copies_all_verify(store, name)
    again = store.scrub()
    assert again["corrupt_copies"] == 0 and again["healed_copies"] == 0


def test_scrub_without_heal_only_quarantines():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    name = omap.object_names()[0]
    hit = fi.flip_bits(name)
    stats = store.scrub(heal=False)
    assert stats["corrupt_copies"] == 1 and stats["healed_copies"] == 0
    assert name not in store.osds[hit].data
    healed = store.scrub()  # now heal
    assert healed["healed_copies"] >= 1
    _copies_all_verify(store, name)


def test_legacy_undigested_objects_are_reported_not_touched():
    store = make_store(4, replicas=2)
    # a pre-digest write: straight to the OSDs, no digest xattr
    for osd_id in store.cluster.locate("old"):
        store.osds[osd_id].put("old", b"legacy bytes", {"version": 1})
    stats = store.scrub()
    assert "old" in stats["undigested"]
    assert stats["corrupt_copies"] == 0
    assert store.get("old") == b"legacy bytes"  # still served


@given(st.lists(st.tuples(st.integers(0, 7), st.integers(0, 2)),
                min_size=1, max_size=10, unique=True))
@settings(max_examples=15, deadline=None)
def test_scrub_is_idempotent_under_random_corruption(pattern):
    """Property: whatever (object, replica) set gets corrupted, one
    healing scrub restores every object with a surviving copy, reports
    every object with none in ``lost``, and a second scrub finds
    NOTHING (no corrupt copies, no heals) — scrub converges."""
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    names = omap.object_names()
    # each distinct copy is hit once: a second XOR would undo the first
    hits: dict[str, set] = {}
    for obj_i, rep_i in pattern:
        name = names[obj_i % len(names)]
        acting = store.cluster.locate(name)
        hits.setdefault(name, set()).add(acting[rep_i % len(acting)])
    for name, osd_ids in hits.items():
        for osd_id in osd_ids:
            fi.flip_bits(name, osd_id=osd_id)
    lost = {n for n, osd_ids in hits.items()
            if len(osd_ids) == len(store.cluster.locate(n))}
    first = store.scrub()
    assert first["corrupt_copies"] == sum(len(v) for v in hits.values())
    assert set(first["lost"]) == lost
    second = store.scrub()
    assert second["corrupt_copies"] == 0
    assert second["healed_copies"] == 0
    for name in names:
        if name not in lost:
            _copies_all_verify(store, name)


# ------------------------------------------------------- verified recover
def test_recover_never_propagates_a_corrupt_replica():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    name = omap.object_names()[0]
    acting = store.cluster.locate(name)
    # corrupt the primary copy, then lose a different replica: recover
    # must source from the surviving VERIFIED copy, never the corrupt one
    fi.flip_bits(name, osd_id=acting[0])
    store.fail_osd(acting[1])
    store.recover()
    for osd_id in store.cluster.locate(name):
        osd = store.osds[osd_id]
        assert content_digest(osd.data[name]) == \
            int(osd.xattrs[name]["digest"])
    out = vol.read(omap, RowRange(0, 500))
    assert np.allclose(out["x"], table["x"][:500])


def test_recover_raises_dataloss_with_names_and_opt_out():
    store, vol, omap, table = make_world()
    fi = FaultInjector(store)
    name = omap.object_names()[0]
    for osd_id in list(store.cluster.locate(name)):
        fi.flip_bits(name, osd_id=osd_id)  # every replica rotten
    with pytest.raises(DataLossError) as ei:
        store.recover()
    assert name in ei.value.objects
    rec = store.recover(allow_loss=True)
    assert rec["objects_lost"] == 1 and name in rec["lost"]


def test_recover_expected_detects_fully_vanished_objects():
    store = make_store(4, replicas=2)
    store.put("a", b"aaaa")
    # "b" never existed on ANY osd — invisible to list_objects, but the
    # caller's inventory (e.g. an ObjectMap) knows it should
    with pytest.raises(DataLossError) as ei:
        store.recover(expected=["a", "b"])
    assert ei.value.objects == ("b",)


# ------------------------------------------------------- fault campaign
def test_randomized_fault_campaign_live_scans_stay_bit_exact():
    """The acceptance scenario: bit-flips + transient failures + one
    slow OSD + one torn write, all at once.  Live scans return
    bit-exact results (zero wrong bytes), scrub detects 100% of the
    injected corruptions and heals them through the chain path, and a
    second scrub is clean."""
    store, vol, omap, table = make_world(
        n=6000, retry=RetryPolicy(attempts=4, base_s=0.0))
    fi = FaultInjector(store)
    rng = np.random.default_rng(42)
    names = omap.object_names()
    victims = rng.choice(len(names), size=3, replace=False)
    for i in victims:  # bit rot on random replicas of random objects
        acting = store.cluster.locate(names[i])
        fi.flip_bits(names[i],
                     osd_id=acting[int(rng.integers(len(acting)))],
                     n_bits=int(rng.integers(1, 8)))
    torn = names[int(rng.choice(
        [i for i in range(len(names)) if i not in victims]))]
    fi.tear_write(torn)  # one torn write
    slow_osd = store.cluster.up_osds[0]
    fi.slow(slow_osd, 0.001)  # one slow OSD
    for osd_id in store.cluster.up_osds[1:3]:
        fi.transient_failures(osd_id, 2)  # gray failures

    # live scans under the campaign: aggregates, filtered scans, reads
    r, _ = vol.query(omap, [oc.op("agg", col="y", fn="count")])
    assert r == float(len(table["y"]))
    s, _ = (vol.scan("t").filter("y", "<", 500).agg("sum", "x")
            .execute(omap))
    assert s == pytest.approx(table["x"][table["y"] < 500].sum(),
                              rel=1e-12)
    out = vol.read(omap, RowRange(100, 4100))
    assert np.array_equal(out["y"], table["y"][100:4100])
    assert np.allclose(out["x"], table["x"][100:4100])

    fi.clear()  # scrub runs in a quiet window
    detected_before = store.fabric.corruptions_detected
    stats = store.scrub()
    # 100% detection: every injected corruption was found (reads may
    # have caught some first; the counter is cumulative either way)
    assert store.fabric.corruptions_detected == fi.corruptions_injected
    assert stats["lost"] == ()
    second = store.scrub()
    assert second["corrupt_copies"] == 0 and second["healed_copies"] == 0
    for name in names:
        _copies_all_verify(store, name)
    # and the cluster serves bit-exact afterwards, faults healed
    out = vol.read(omap, RowRange(0, len(table["y"])))
    assert np.allclose(out["x"], table["x"])


# ------------------------------------------------------- ckpt reconcile
def test_partial_save_reconciles_to_bit_exact_checkpoint():
    """``save`` killed mid-stream: the PartialWriteError's persisted
    listing is sufficient to delete-and-retry to a bit-exact
    checkpoint, and the torn save is invisible to restore."""
    store = make_store(4, replicas=2)
    state = {"w": np.arange(9000, dtype=np.float64),
             "b": np.linspace(-1, 1, 5000, dtype=np.float32)}
    policy = PartitionPolicy(target_object_bytes=8 << 10,
                             max_object_bytes=64 << 10)

    real_put_batch = store.put_batch

    def killed_put_batch(names, blobs, xattrs=None, **kw):
        it = iter(blobs)  # producer dies after half the sub-writes
        return real_put_batch(
            names, (b for _, b in zip(range(len(names) // 2), it)),
            xattrs, **kw)

    store.put_batch = killed_put_batch
    with pytest.raises(PartialWriteError) as ei:
        ckpt.save(store, state, 1, policy=policy, window_bytes=16 << 10)
    store.put_batch = real_put_batch

    assert ei.value.persisted  # it tells us exactly what landed
    assert ckpt.latest_step(store) is None  # torn save is invisible
    deleted = ckpt.reconcile_partial_save(store, ei.value)
    assert sorted(deleted) == sorted(n for n, _ in ei.value.persisted)
    assert not any(n.startswith("ckpt/") for n in store.list_objects())

    ckpt.save(store, state, 1, policy=policy, window_bytes=16 << 10)
    like = {"w": np.empty_like(state["w"]), "b": np.empty_like(state["b"])}
    restored, manifest = ckpt.restore(store, like)
    assert np.array_equal(restored["w"], state["w"])
    assert np.array_equal(restored["b"], state["b"])
    assert manifest["step"] == 1


# ------------------------------------------------- row-slice refresh
def test_row_sliced_plan_refreshes_names_after_repartition():
    """ROADMAP standing item: an object whose extent GREW into a row
    range after a re-partition is contacted at execute time — the plan
    stamps the ObjectMap version and re-derives its targets when the
    map moved."""
    n = 4000
    rng = np.random.default_rng(3)
    ds = LogicalDataset("rr", (Column("v", "float64"),), n, 64)
    store = make_store(5, replicas=2)
    vol = GlobalVOL(store)
    fine = vol.create(ds, PartitionPolicy(target_object_bytes=4 << 10,
                                          max_object_bytes=4 << 13))
    table = {"v": rng.normal(size=n)}
    vol.write(fine, table)
    assert fine.n_objects > 2

    s = vol.scan("rr").rows(500, 1500).agg("count", "v")
    plan = s.explain(fine)
    assert plan.omap_version == fine.version
    assert len(plan.names) < fine.n_objects  # targeted subset

    # re-partition coarse: obj.000000's extent GROWS to cover the whole
    # range; the fine map's extra objects vanish
    coarse = vol.create(ds, PartitionPolicy(
        target_object_bytes=(n * 8) << 1, max_object_bytes=(n * 8) << 2))
    assert coarse.version > fine.version
    vol.write(coarse, table)
    for name in set(fine.object_names()) - set(coarse.object_names()):
        store.delete(name)  # a real re-partition retires stale objects

    # the OLD compiled plan, executed standalone (no caller-held map):
    # one version probe notices the move and re-derives the targets
    r, stats = vol.engine.execute(plan)
    assert r == 1000.0
    # a fresh hint that matches the current map skips the probe
    plan2 = s.explain(coarse)
    store.fabric.reset()
    r2, _ = vol.engine.execute(plan2, omap=coarse)
    assert r2 == 1000.0
    assert store.fabric.xattr_ops == 0
