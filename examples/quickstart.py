"""Quickstart: the paper's system in ~80 lines.

1. stand up a replicated object store (Ceph stand-in)
2. map a logical dataset onto objects through the GlobalVOL
3. run storage-side scans through the composable builder
   (filters AND together, aggregates compose, pruning happens ON the
   OSDs, table results come back as one framed response per OSD)
4. stream a windowed ingest: encode overlaps the NIC, replicas chain
5. survive failures: fail-stop OSD loss, injected bit rot (digest
   verify + scrub/heal), torn writes, and transient gray failures
   (bounded-backoff retries; loud DataLossError when data is truly gone)
6. train a tiny LM whose data path IS that object store (the loader's
   windowed fetch assembles early batches while slow OSDs still serve)
7. (…and serve it hot: OSD result caches + single-flight sessions)
8. slice an N-d array: numpy-style hyperslab selections resolved ON
   the OSDs (chunked dataspaces, per-chunk zone-map pruning — wire
   bytes track the selection, not the array)

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import (Column, FaultInjector, GlobalVOL, LogicalDataset,
                        PartitionPolicy, RowRange, make_store)

# -- 1. an 8-OSD cluster, 3-way replication ------------------------------
store = make_store(8, replicas=3)
vol = GlobalVOL(store)

# -- 2. map a dataset to objects ------------------------------------------
ds = LogicalDataset(
    "sensors",
    (Column("temp", "float32"), Column("station", "int32")),
    n_rows=100_000, unit_rows=512)
omap = vol.create(ds, PartitionPolicy(target_object_bytes=64 << 10))
rng = np.random.default_rng(0)
vol.write(omap, {
    "temp": rng.normal(15, 8, ds.n_rows).astype(np.float32),
    "station": rng.integers(0, 50, ds.n_rows).astype(np.int32),
})
print(f"mapped {ds.n_rows} rows -> {omap.n_objects} objects on "
      f"{len(store.cluster.osds)} OSDs")

# -- 3. composable pushdown scans -----------------------------------------
stats_hot, stats = (vol.scan("sensors")
                    .filter("station", "==", 7)
                    .agg("mean", "temp").agg("count", "temp")
                    .execute())
print(f"mean(temp | station==7) = {stats_hot['mean(temp)']:.3f} over "
      f"{stats_hot['count(temp)']:.0f} rows  "
      f"[{stats['client_rx']} B moved, {stats['local_bytes']} B scanned "
      f"storage-side, {stats['exec_class']}, zero zone-map round trips "
      f"({stats['xattr_ops']})]")

cold, stats = (vol.scan("sensors").filter("temp", "<", -20)
               .project("temp", "station").execute())
print(f"filter→project: {stats['result_rows']} matching rows back in "
      f"{stats['rx_frames']} framed responses "
      f"({stats['objects_pruned']} objects pruned ON their OSDs)")

med, qstats = vol.scan("sensors").median("temp", approx=True).execute()
print(f"median(temp) ~= {med:.3f}  [approx sketch, "
      f"{qstats['client_rx']} B moved, pushdown={qstats['pushdown']}]")

# the no-pushdown baseline: the same pipeline compiled as client-gather
# (whole objects to the client, evaluated there) — what pushdown saves
hot_scan = vol.scan("sensors").filter("station", "==", 7).agg("mean", "temp")
pushed, pstats = hot_scan.execute()
base, bstats = vol.engine.execute(vol.engine.compile_ops(
    omap, hot_scan.pipeline(), baseline=True))
print(f"client-side baseline: mean {base:.3f} (pushdown {pushed:.3f}), "
      f"{bstats['client_rx']} B moved against {pstats['client_rx']} B")

# -- 3b. expression filters + OSD-side row ranges --------------------------
# filters are a full predicate ALGEBRA (core.expr): OR-groups, IN-lists,
# ranges, negations, string prefixes — the whole tree ships serialized
# inside the batched request, each OSD evaluates it with vectorized
# masks AND prunes with interval arithmetic against its own current
# zone maps (an Or prunes only when EVERY branch provably misses; Not
# never prunes — conservative by construction, so prune="client" and
# prune="pushdown" always agree)
extremes, stats = (vol.scan("sensors")
                   .or_(("temp", "<", -10), ("temp", ">", 40))
                   .isin("station", [7, 11, 13])
                   .project("temp", "station").execute())
print(f"OR/IN scan: {stats['result_rows']} extreme rows from 3 stations "
      f"in {stats['rx_frames']} frames, {stats['objects_pruned']} objects "
      f"pruned ON their OSDs, {stats['xattr_ops']} zone-map round trips")

# .rows() ships as a row_slice op carrying GLOBAL rows: each OSD
# resolves its objects' sub-ranges from their own extent xattrs at
# execute time, so one compiled plan keeps serving the right rows even
# after the dataset is re-partitioned — and a row-ranged aggregate now
# rides the same per-OSD combine plane as a whole-table scan
windowed, stats = (vol.scan("sensors").rows(10_000, 60_000)
                   .filter("temp", ">", 20).agg("mean", "temp")
                   .execute())
print(f"rows[10k:60k] mean(temp|>20) = {windowed:.2f}  "
      f"[{stats['exec_class']}, prune={stats['prune']}]")

# -- 4. streaming pipelined ingest ----------------------------------------
# with a transport model (shared client NIC, per-OSD disks) vol.write
# STREAMS: per-OSD sub-write groups flush as the encoder produces
# blobs, so encode overlaps the NIC instead of running ahead of it, and
# each replica write pipelines entry -> replica -> replica (chain), so
# the entry OSD sends each blob once.
sim = make_store(4, replicas=3, client_bw=400 << 20, disk_bw=200 << 20)
svol = GlobalVOL(sim)
sds = LogicalDataset("stream_demo",
                     (Column("tokens", "int32", (64,)),),
                     n_rows=20_000, unit_rows=512)
somap = svol.create(sds, PartitionPolicy(target_object_bytes=1 << 20))
sim.fabric.reset()
svol.write(somap, {"tokens": rng.integers(0, 1 << 15, (20_000, 64))
                   .astype(np.int32)}, window_bytes=256 << 10)
f = sim.fabric
print(f"streamed ingest: {f.ops} put requests (one per OSD) in "
      f"{f.stream_windows} windows, {f.overlap_s * 1e3:.0f}ms encode "
      f"hidden behind the NIC; chain replication: entry OSD egress "
      f"{f.entry_egress_bytes >> 20}MB of {f.replica_bytes >> 20}MB "
      f"total replica traffic")

# -- 5. surviving failures -------------------------------------------------
# 5a. fail-stop: kill an OSD, peering re-replicates from digest-
# verified survivors.  recover() is LOUD about real data loss: it
# raises DataLossError naming the objects (allow_loss=True opts back
# into the stats-only behavior for benchmarks).
victim = store.cluster.primary(omap.object_names()[0])
store.fail_osd(victim)
rec = store.recover()
rows = vol.read(omap, RowRange(0, 5))
print(f"killed {victim}: recovered {rec['objects_moved']} replicas, "
      f"lost {rec['objects_lost']}; reads fine: temp[:5]="
      f"{np.round(rows['temp'], 2)}")

# 5b. gray failures: every write stamped a content digest into the
# object's xattrs (put, batched windows, every replica-chain hop), so
# every copy is independently verifiable.  Inject bit rot on a primary
# copy: the read digest-checks it, quarantines the bad copy on its
# OSD, and fails over to a verified replica — bit-exact, zero wrong
# bytes to the client.
hit = omap.extents[1]
target = hit.name
fi = FaultInjector(store)
fi.flip_bits(target, osd_id=store.cluster.locate(target)[0], n_bits=3)
_ = vol.read(omap, hit.rows)  # served from a verified replica
print(f"bit rot on {target}'s primary: read stayed bit-exact, "
      f"{store.fabric.corruptions_detected} corruption detected + "
      f"quarantined")

# scrub() is the maintenance half: walk every OSD, verify each copy
# against its digest, quarantine divergent/torn copies, heal from the
# highest-version verified source through the replication chain.  A
# second scrub finds nothing (idempotent).
fi.tear_write(omap.object_names()[2])  # blob landed, xattrs lost
sc = store.scrub()
print(f"scrub: {sc['objects_scrubbed']} objects verified "
      f"({store.fabric.scrub_bytes >> 20} MB), {sc['corrupt_copies']} "
      f"corrupt/torn copies found, {sc['healed_copies']} healed through "
      f"the chain; second scrub finds "
      f"{store.scrub()['corrupt_copies']}")

# 5c. retry/deadline knobs: transient faults (an OSD failing N requests
# then recovering) are retried with bounded exponential backoff under a
# per-request deadline — RetryPolicy(attempts, base_s, cap_s,
# deadline_s) on make_store(retry=...).  Exhaustion fails over to the
# next replica; only when EVERY replica is lost or corrupt does the
# client see a DataLossError naming the objects.
fi.transient_failures(store.cluster.up_osds[0], 2)
n_all, _ = vol.scan("sensors").agg("count", "temp").execute()
print(f"transient faults: scan retried ({store.fabric.retries} retries) "
      f"and still counted {n_all:.0f} rows")

# -- 6. serving hot data: OSD caches + single-flight sessions --------------
# a serving cluster sees the SAME scans from thousands of clients.
# cache_bytes gives every OSD a byte-bounded LRU of decoded columns and
# pipeline results keyed by (object, xattr version, pipeline digest) —
# the monotonic version stamped by every write path makes invalidation
# exact, so a rewrite/heal/quarantine can never serve a stale byte.
# scan_bw models the per-OSD decode service queue; cache hits skip it.
import threading

from repro.core import ScanSession

hot = make_store(4, replicas=2, scan_bw=200 << 20, cache_bytes=32 << 20)
hvol = GlobalVOL(hot)
hds = LogicalDataset("hotset", (Column("temp", "float64"),
                                Column("station", "int32")),
                     n_rows=40_000, unit_rows=512)
homap = hvol.create(hds, PartitionPolicy(target_object_bytes=128 << 10))
hvol.write(homap, {"temp": rng.normal(15.0, 8.0, 40_000),
                   "station": rng.integers(0, 500, 40_000)
                   .astype(np.int32)})
q = hvol.scan("hotset").filter("station", "<", 100).project("temp")
q.execute()                     # cold: every OSD decodes from device
b0, w0 = hot.fabric.local_bytes, hot.fabric.queue_wait_s
q.execute()                     # warm: served from the OSD caches
print(f"hot repeat: {hot.fabric.cache_hits} cache hits, "
      f"{hot.fabric.local_bytes - b0} new bytes decoded, "
      f"{(hot.fabric.queue_wait_s - w0) * 1e3:.1f}ms queue wait — "
      f"hits skip the service queue entirely")

# the client half: a ScanSession single-flights identical concurrent
# scans (N clients, ONE OSD round trip, result fanned out N ways) and
# coalesces same-scan different-column requests into one widened fetch
sess = ScanSession(hvol, window_s=0.02)
agg = hvol.scan("hotset").filter("temp", ">", 20.0).agg("count", "temp")
ops0 = hot.fabric.ops
clients = [threading.Thread(target=sess.execute, args=(agg,))
           for _ in range(8)]
for c in clients:
    c.start()
for c in clients:
    c.join()
print(f"single-flight: 8 identical concurrent scans -> "
      f"{sess.stats['executed']} execution "
      f"({hot.fabric.ops - ops0} requests — one scan's worth), "
      f"{sess.stats['deduped']} served by fan-out")

# -- 7. train a tiny LM straight off the store -----------------------------
from repro.configs.base import get_config
from repro.data.corpus import CorpusSpec, build_corpus
from repro.data.pipeline import ObjectDataLoader
from repro.models.archs import build_model
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig

cfg = get_config("yi_9b", smoke=True)
build_corpus(vol, CorpusSpec(n_seqs=256, seq_len=128,
                             vocab_size=cfg.vocab_size))
model = build_model(cfg, remat="none")
# window_steps=2: the loader fetches two steps' rows in one streaming
# gather and assembles each batch the moment ITS frames land
loader = ObjectDataLoader(vol, "corpus", global_batch=8, packed=True,
                          window_steps=2)
trainer = Trainer(model, loader, store, opt=OptConfig(lr=1e-3),
                  cfg=TrainerConfig(total_steps=20, ckpt_every=10,
                                    log_every=5, packed_ingest=True))
trainer.run()
print(f"trained 20 steps off the object store "
      f"(loss {trainer.history[0]['loss']:.2f} -> "
      f"{trainer.history[-1]['loss']:.2f}); checkpoints are objects too: "
      f"{len(store.list_objects('ckpt/'))} stored")

# -- 8. N-d arrays: hyperslab selection pushdown ---------------------------
# scientific datasets are chunked N-d arrays, not tables.  A Dataspace
# maps chunks onto objects; numpy-style selections compile to ONE
# GLOBAL hyperslab op that every OSD resolves against its own 'chunks'
# xattr (late binding — re-partition the array and compiled plans keep
# serving correct cells), and a predicate prunes whole chunks from
# per-chunk zone maps before any cell is decoded.
from repro.core import Cmp, Dataspace

cube = Dataspace(name="cube", shape=(64, 64, 32), dtype="float64",
                 chunk=(16, 16, 8))
field = rng.uniform(0.0, 1.0, cube.shape)
field[:16, :16, :8] += 100.0                      # one hot corner
cmap = vol.create_array(cube, PartitionPolicy(
    target_object_bytes=256 << 10))
vol.write_array(cmap, field)
view = vol.array("cube")

store.fabric.reset()
sub = view[8:56:2, ::4, 5]                        # strided 2-d slice
assert np.array_equal(sub, field[8:56:2, ::4, 5])
print(f"hyperslab [8:56:2, ::4, 5]: {sub.size} cells in "
      f"{store.fabric.rx_frames} framed responses, "
      f"{store.fabric.client_rx} B on the wire "
      f"(the full array is {field.nbytes} B)")

store.fabric.reset()
hot_cells = view.sel(np.s_[:, :, :], where=Cmp("data", ">", 50.0))
print(f"where data>50: {store.fabric.chunks_pruned} cold chunks pruned "
      f"ON the OSDs from per-chunk zone maps "
      f"({store.fabric.xattr_ops} client zone-map round trips)")

# -- 9. keeping the cluster healthy ----------------------------------------
# long-lived clusters stay healthy through the maintenance plane: a
# continuous scrub walker (rate-limited digest verify + heal), a
# small-object compactor (folds one-blob-per-append streams into
# target-sized objects and rewrites the .objmap with a version bump —
# compiled plans re-target on their next execute), a live rebalancer
# (copy-verify-drop toward the current placement after topology
# changes), and versioned GC (reclaims replaced members + quarantined
# copies after an operator-confirmed retention window).  All of it
# runs WHILE the serve plane keeps answering, bit-exactly.
from repro.core import Column, LogicalDataset, MaintenancePlane

stream = LogicalDataset("stream", (Column("v", "float64"),), 4096, 32)
smap = vol.create(stream, PartitionPolicy(target_object_bytes=32 * 8))
svals = rng.normal(size=4096)
vol.write(smap, {"v": svals})            # 1 tiny object per append
n_small = smap.n_objects

plane = MaintenancePlane(
    store, scrub_rate_bytes_s=512e6,     # trickle, don't burst
    compact_policy=PartitionPolicy(target_object_bytes=48 << 10),
    compact_datasets=["stream"], gc_retention_s=0.1)
plane.start()                            # all four daemons
plane.confirm_gc()                       # operator signs off on GC

import time
prev = -1
while plane.compact_runs != prev:        # let compaction settle
    prev = plane.compact_runs
    time.sleep(0.05)
fi.flip_bits(vol.open("stream").object_names()[0])  # rot a live copy
while plane.scrub_corrupt == 0:          # the walker finds + heals it
    time.sleep(0.01)
live = vol.read(vol.open("stream"), RowRange(0, 4096))  # serve plane
assert np.array_equal(live["v"], svals), "maintenance must be invisible"
time.sleep(0.15)                         # retention window passes
plane.gc_step()                          # (or just leave the daemon to it)
plane.stop()
print(f"maintenance plane: compacted {n_small} tiny objects -> "
      f"{vol.open('stream').n_objects}, walker detected+healed "
      f"{plane.scrub_corrupt} rotten copy, GC reclaimed "
      f"{store.fabric.gc_objects} retired objects "
      f"({store.fabric.gc_bytes >> 10} KB) — live reads stayed bit-exact")

# -- 10. verifying the invariants ------------------------------------------
# Everything above leans on contracts no unit test can enforce: Fabric
# counters are caller-thread-owned, _GUARDED_BY state only moves under
# its lock, every write path stamps a digest and invalidates caches,
# every objclass op round-trips the wire.  The verification plane
# checks them structurally — run it like CI does:
#
#   PYTHONPATH=src python -m repro.analysis        # static AST linter
#   PYTHONPATH=src python -m pytest tests/test_serve_plane.py \
#       tests/test_maintenance.py -q --lockcheck   # lock-order harness
#
# The linter must exit 0 with zero unsuppressed findings (intentional
# exceptions live in src/repro/analysis/suppressions.txt, each with a
# justification); --lockcheck fails the suite on any lock-order cycle
# or unlocked guarded mutation, even if nothing deadlocked.  Here we
# just run the registry pass in-process: every registered op either
# rides a merge plane or is explicitly declared not to.
from repro.analysis.registry import check_registry
from repro.core.objclass import registered_ops

assert check_registry() == [], "objclass registry contract broken"
print(f"verification plane: registry contracts hold for "
      f"{len(registered_ops())} objclass ops "
      f"(run `python -m repro.analysis` for the full linter)")
