"""Chip smoke test: the store's scan path and its training input path on
one TPU, through the entry points a user calls.

    python chip_smoke.py                          # full size; needs a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny # CPU rehearsal

Phases, in order; the first failure ends the run with a non-zero exit:

  device  the default JAX device must be a TPU.  Prints its kind, the
          device count and the compile-cache directory in use.
  scan    an 8-OSD store (3 replicas, default 8 MiB objects) holding a
          2^25-row table generated from --seed (key int32 < 2^17, which
          bitpacks to 17 bits; x, y float32), queried through
          ``vol.scan``: a pushed-down filter + sum/count/min/max, an
          IN/OR predicate aggregate, and a row-range projection, each
          checked against numpy on the same table.  The key column must
          be decoded by the compiled bitunpack kernel.
  train   the ``examples/train_e2e`` flow at its 100m preset: a few
          packed-ingest steps with finite losses, one OSD killed and
          recovered, one checkpoint; a fresh Trainer on the same store
          must restore the saved state bit for bit.

The last line of stdout is one JSON object naming the device; it is
printed only when every phase passed.  Walls printed on earlier lines
are smoke timings on the host clock, not benchmark numbers.

``--tiny`` cuts every size for a CPU rehearsal: the scan then runs the
same kernel in interpret mode, every phase still checks its results,
and a missing TPU fails the run at the end instead of the start.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from repro.core import (Column, GlobalVOL, LogicalDataset,  # noqa: E402
                        PartitionPolicy, make_store)
from repro.core import format as fmt  # noqa: E402
from repro.core.format import block_header  # noqa: E402
from repro.kernels.bitunpack import (decode_stats,  # noqa: E402
                                     reset_decode_stats)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _approx(got: float, want: float, rel: float = 1e-9) -> bool:
    return abs(got - want) <= rel * max(abs(want), 1e-300)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def scan_phase(rows_log2: int, seed: int, on_tpu: bool, *,
               object_bytes: int | None = None) -> None:
    n = 1 << rows_log2
    rng = np.random.default_rng(seed)
    table = {"key": rng.integers(0, 1 << 17, n, dtype=np.int32),
             "x": rng.standard_normal(n, dtype=np.float32),
             "y": rng.standard_normal(n, dtype=np.float32)}
    x64 = table["x"].astype(np.float64)
    y64 = table["y"].astype(np.float64)

    store = make_store(8)
    vol = GlobalVOL(store)
    policy = (PartitionPolicy() if object_bytes is None else
              PartitionPolicy(target_object_bytes=object_bytes))
    ds = LogicalDataset("smoke", (Column("key", "int32"),
                                  Column("x", "float32"),
                                  Column("y", "float32")),
                        n_rows=n, unit_rows=1 << (rows_log2 - 10))
    t0 = time.perf_counter()
    omap = vol.create(ds, policy)
    encoded = vol.write(omap, table)
    wall_write = time.perf_counter() - t0
    stored = sum(store.stats()["osd_bytes"].values())
    codecs = {c["codec"] for e in omap.extents
              for c in block_header(store.get(e.name))["columns"]
              if c["name"] == "key"}
    _check(codecs == {"bitpack17"}, f"key codecs {codecs}")
    print(f"[scan] table: {n} rows in {omap.n_objects} objects, "
          f"{encoded} B encoded, {stored} B stored "
          f"over {len(store.cluster.osds)} OSDs x "
          f"{store.cluster.replicas} replicas; key codec bitpack17; "
          f"write {wall_write:.3f} s (smoke timing)")

    if not on_tpu:  # rehearsal: the same kernel, interpreted
        fmt.set_bitunpack_backend("device")
    reset_decode_stats()
    try:
        # 1. pushed-down filter on key, then sum/count/min/max of x
        thr = 1 << 14
        t0 = time.perf_counter()
        got, stats = (vol.scan("smoke").filter("key", "<", thr)
                      .agg("sum", "x").agg("count", "x")
                      .agg("min", "x").agg("max", "x").execute())
        wall = time.perf_counter() - t0
        m = table["key"] < thr
        _check(stats["exec_class"] == "osd-combine", stats["exec_class"])
        _check(got["count(x)"] == int(m.sum()), "filter count")
        _check(_approx(got["sum(x)"], x64[m].sum()), "filter sum")
        _check(got["min(x)"] == float(table["x"][m].min()), "filter min")
        _check(got["max(x)"] == float(table["x"][m].max()), "filter max")
        print(f"[scan] filter key<{thr} -> sum/count/min/max(x): "
              f"count={int(got['count(x)'])} sum={got['sum(x)']!r} "
              f"== numpy; {stats['exec_class']}, {wall:.3f} s "
              f"(smoke timing)")

        # 2. IN-list on key AND an OR-group over x / y, aggregated
        vals = rng.choice(1 << 17, 512, replace=False).astype(np.int32)
        t0 = time.perf_counter()
        got, stats = (vol.scan("smoke").isin("key", vals.tolist())
                      .or_(("x", "<", -1.0), ("y", ">", 1.0))
                      .agg("sum", "y").agg("count", "y").execute())
        wall = time.perf_counter() - t0
        m = np.isin(table["key"], vals) & ((table["x"] < -1.0)
                                           | (table["y"] > 1.0))
        _check(got["count(y)"] == int(m.sum()), "isin/or count")
        _check(_approx(got["sum(y)"], y64[m].sum()), "isin/or sum")
        print(f"[scan] isin(key, 512 values) & (x<-1 | y>1) -> "
              f"count={int(got['count(y)'])} sum={got['sum(y)']!r} "
              f"== numpy; {stats['exec_class']}, {wall:.3f} s "
              f"(smoke timing)")

        # 3. row-range table-out projection of key and x
        a, b = n // 3, n // 3 + n // 4
        t0 = time.perf_counter()
        got, stats = (vol.scan("smoke").rows(a, b).project("key", "x")
                      .execute())
        wall = time.perf_counter() - t0
        _check(np.array_equal(got["key"], table["key"][a:b]), "rows key")
        _check(np.array_equal(got["x"], table["x"][a:b]), "rows x")
        print(f"[scan] rows[{a}:{b}] project(key, x): {b - a} rows "
              f"bit-equal to numpy; {stats['exec_class']}, "
              f"{wall:.3f} s (smoke timing)")
    finally:
        fmt.set_bitunpack_backend("auto")

    ds_stats = decode_stats()
    _check(ds_stats["calls"] > 0, "bitunpack kernel never ran")
    if on_tpu:
        _check(ds_stats["interpret_calls"] == 0,
               "bitunpack ran in interpret mode on a TPU")
    print(f"[scan] bitunpack kernel: {ds_stats['calls']} calls, "
          f"{ds_stats['interpret_calls']} in interpret mode, "
          f"{ds_stats['shapes']} distinct launch shapes")


def train_phase(preset: str, steps: int, seed: int) -> None:
    import jax

    from examples.train_e2e import (PRESETS, build_store, kill_and_recover,
                                    make_cfg, make_trainer)

    p = PRESETS[preset]
    cfg = make_cfg(p)
    print(f"[train] preset {preset}: {cfg.param_count() / 1e6:.1f}M "
          f"params, batch {p['batch']} x seq {p['seq']}, {steps} steps")
    store, vol = build_store(p, cfg, steps, seed)
    trainer, loader = make_trainer(p, cfg, store, vol, steps, seed,
                                   ckpt_every=steps)
    kill_at = steps // 2
    recovered = []

    def on_step(step: int) -> None:
        if step == kill_at:
            recovered.append(kill_and_recover(store, step))

    t0 = time.perf_counter()
    try:
        state = trainer.run(on_step=on_step)
    finally:
        loader.close()
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in trainer.history]
    _check(len(losses) == steps, f"{len(losses)} of {steps} steps")
    _check(bool(np.all(np.isfinite(losses))), f"losses {losses}")
    _check(len(recovered) == 1 and recovered[0]["objects_lost"] == 0,
           f"recovery {recovered}")
    _check(trainer.ckpts.saved_steps == [steps],
           f"checkpoints {trainer.ckpts.saved_steps}")
    print(f"[train] losses {[round(x, 4) for x in losses]} (all finite); "
          f"{wall:.3f} s incl. compile (smoke timing)")

    saved = jax.device_get(state)
    fresh, fresh_loader = make_trainer(p, cfg, store, vol, steps, seed,
                                       ckpt_every=steps)
    try:
        restored, step = fresh.init_or_restore()
    finally:
        fresh_loader.close()
    restored = jax.device_get(restored)
    _check(step == steps, f"restored step {step}")
    _check(jax.tree.structure(restored) == jax.tree.structure(saved),
           "restored tree structure")
    leaves = list(zip(jax.tree.leaves(saved), jax.tree.leaves(restored)))
    for a, b in leaves:
        a, b = np.asarray(a), np.asarray(b)
        _check(a.dtype == b.dtype and a.shape == b.shape
               and a.tobytes() == b.tobytes(), "restored leaf differs")
    nbytes = sum(np.asarray(a).nbytes for a, _ in leaves)
    print(f"[train] fresh Trainer restored step {step}: {len(leaves)} "
          f"leaves, {nbytes} B, bit-equal to the saved state")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal sizes (a 2^16-row table, the "
                         "tiny preset); fails at the end without a TPU")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    print(f"[device] platform={device['platform']} kind={device['kind']!r} "
          f"count={device['count']} compile_cache={cache}")
    if not on_tpu and not args.tiny:
        raise SystemExit(f"[device] FAIL: no TPU (default device is "
                         f"{dev.platform})")

    if args.tiny:
        scan_phase(16, args.seed, on_tpu, object_bytes=64 << 10)
        train_phase("tiny", 4, args.seed)
    else:
        scan_phase(25, args.seed, on_tpu)
        train_phase("100m", 6, args.seed)

    n_cached = sum(1 for _ in pathlib.Path(cache).glob("*")) \
        if pathlib.Path(cache).is_dir() else 0
    print(f"[device] compile cache {cache}: {n_cached} entries")
    if not on_tpu:
        raise SystemExit("[device] FAIL: no TPU; the tiny rehearsal "
                         "phases passed")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
