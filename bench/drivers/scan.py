"""Scan traffic: one client, closed loop, one request at a time.

The configuration names a table generator (``bench/gen/<generator>.py``)
and the store it lives in; the traffic file says what each request
asks.  Keys of a scan traffic file:

``filter``      list of ``{"col", "cmp", "quantile"}``: the threshold is
                that quantile of the generated column
``project``     column names, or null for every column
``aggregates``  list of ``[fn, col]``, or null for a table-out scan
``rows``        null (the whole table), or ``{"length": L}``: each
                request reads ``L`` rows from a start drawn uniformly
                from the seed
``keep``        how many answers to keep and check, drawn from the seed
                by reservoir sampling; ``"all"`` keeps every one
``warmup``      ``"request"`` sends one request before the window;
                ``"each_object"`` sends one row range starting at each
                object's first row, which launches every decode shape a
                range can use

Each request goes through ``GlobalVOL.scan(...)``: ``Scan.explain``
(span ``scan.plan``) and ``ScanEngine.execute`` (span ``scan.execute``)
with the client's object map, as ``Scan.execute`` does.  Its latency is
taken on the host clock from building the scan to holding the answer.
"""

from __future__ import annotations

import math
import pathlib
import time

import numpy as np

from bench import load_module

BENCH = pathlib.Path(__file__).resolve().parents[1]


def load_generator(name: str):
    return load_module(BENCH / "gen" / f"{name}.py", f"bench_gen_{name}")


def requests(traffic: dict, n_rows: int, seed: int):
    """Endless stream of requests from the seed, each its row range
    ``(a, b)`` or None for the whole table: every seed sends the same
    shape of request, only the starts of row ranges differ."""
    spec = traffic.get("rows")
    if spec is None:
        while True:
            yield None
    length = min(int(spec["length"]), n_rows)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5CA7]))
    while True:
        for a in rng.integers(0, n_rows - length + 1, 4096):
            yield int(a), int(a) + length


def thresholds(traffic: dict, table: dict) -> list[tuple[str, str, float]]:
    return [(f["col"], f["cmp"],
             float(np.quantile(table[f["col"]], f["quantile"])))
            for f in traffic.get("filter") or []]


def build_scan(vol, dataset: str, traffic: dict, filters, rows):
    s = vol.scan(dataset)
    if rows is not None:
        s = s.rows(*rows)
    for col, cmp, value in filters:
        s = s.filter(col, cmp, value)
    if traffic.get("project"):
        s = s.project(*traffic["project"])
    for fn, col in traffic.get("aggregates") or []:
        s = s.agg(fn, col)
    return s


class Reservoir:
    """Keeps ``k`` of the items offered (all when ``k`` is None), drawn
    uniformly by the seed (algorithm R)."""

    def __init__(self, k: int | None, seed: int):
        self.k = k
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0x4E5]))
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        if self.k is None or len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def run(ctx) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core import (Column, GlobalVOL, LogicalDataset,
                            PartitionPolicy, make_store)
    from repro.kernels.bitunpack import decode_stats

    cfg, traffic, seed = ctx.cfg, ctx.traffic, ctx.args.seed
    table = load_generator(cfg["generator"]).generate(cfg["data"], seed)
    n = len(next(iter(table.values())))
    st = cfg["store"]
    store = make_store(int(st["osds"]), replicas=int(st["replicas"]),
                       cache_bytes=int(st.get("cache_bytes", 0)))
    vol = GlobalVOL(store)
    ds = LogicalDataset(cfg["dataset"],
                        tuple(Column(k, v.dtype.str) for k, v
                              in table.items()),
                        n_rows=n, unit_rows=int(cfg["data"]["unit_rows"]))
    omap = vol.create(ds, PartitionPolicy(
        target_object_bytes=int(st["object_bytes"])))
    vol.write(omap, table)
    ctx.log(f"table: {n} rows in {omap.n_objects} objects, "
            f"{store.cluster.replicas} replicas on {len(store.osds)} OSDs")

    filters = thresholds(traffic, table)
    stream = requests(traffic, n, seed)

    def send(rows):
        scan = build_scan(vol, ds.name, traffic, filters, rows)
        with TraceAnnotation("scan.plan"):
            plan = scan.explain(omap)
        with TraceAnnotation("scan.execute"):
            before = store.fabric.snapshot()
            out, stats = vol.engine.execute(plan, before=before, omap=omap)
        return plan, out, stats

    # warm-up: every decode shape the traffic can launch
    if traffic.get("warmup") == "each_object":
        length = min(int(traffic["rows"]["length"]), n)
        for e in omap:
            a = min(e.row_start, n - length)
            send((a, a + length))
    else:
        send(next(requests(traffic, n, seed)))
    setup_s = time.perf_counter() - ctx.t0

    keep = traffic.get("keep", "all")
    kept = Reservoir(None if keep == "all" else int(keep), seed)
    lat: list[float] = []
    rows_covered = 0
    result_rows = 0
    failed = 0
    kernel_bytes = 0.0
    plans: dict = {}
    fab0 = store.fabric.snapshot()
    dec0 = decode_stats()
    if ctx.trace_dir is not None:
        jax.profiler.start_trace(str(ctx.trace_dir))
    ctx.compiles.on = True
    t_begin = time.perf_counter()
    with TraceAnnotation("window"):
        while time.perf_counter() - t_begin < ctx.args.seconds:
            rows = next(stream)
            t_a = time.perf_counter()
            try:
                plan, out, stats = send(rows)
            except Exception as e:  # counted, and the run is not correct
                failed += 1
                ctx.log(f"request failed: {type(e).__name__}: {e}")
                continue
            lat.append(time.perf_counter() - t_a)
            rows_covered += n if rows is None else rows[1] - rows[0]
            result_rows += int(stats["result_rows"] or 0)
            ops, count = plans.get(plan.names, (plan.exec_ops, 0))
            plans[plan.names] = (ops, count + 1)
            if stats["objects_pruned"]:
                kernel_bytes = math.nan  # decoded objects unknown
            kept.offer((rows, out))
    t_end = time.perf_counter()
    ctx.compiles.on = False
    if ctx.trace_dir is not None:
        jax.profiler.stop_trace()
    window_s = t_end - t_begin
    fab1 = store.fabric.snapshot()
    dec1 = decode_stats()

    peak = ctx.memory_peak()
    done = len(lat)
    ctx.log(f"window {window_s:.3f} s: {done} requests, {failed} failed; "
            f"latency median {np.median(lat) * 1e3:.3f} ms, "
            f"p95 {np.percentile(lat, 95) * 1e3:.3f} ms" if lat else
            f"window {window_s:.3f} s: no request completed")
    if ctx.trace_dir is not None and not math.isnan(kernel_bytes):
        kernel_bytes = decode_kernel_bytes(store, plans, ctx)

    # correctness: every kept answer against the plain reference, and
    # every object's acknowledged write on as many verified replicas
    # as the configuration states
    checks = ctx.ref.check(table, traffic, filters, kept.items)
    census = store.copy_census([e.name for e in omap])
    short = sum(1 for c in census.values()
                if len(c["verified"]) < int(st["replicas"]))
    checks["under_replicated_objects"] = {"value": short, "limit": 0}
    correct = failed == 0 and done > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    store.close()

    e2e = {"setup_s": setup_s}
    if done:
        e2e["scan_rows_per_s"] = rows_covered / window_s
        e2e["scan_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    counters = {
        "requests": done,
        "result_rows": result_rows,
        "client_rx_bytes": fab1["client_rx"] - fab0["client_rx"],
        "decode_calls": dec1["calls"] - dec0["calls"],
        "interpret_calls": dec1["interpret_calls"] - dec0["interpret_calls"],
        "bitunpack_bytes": kernel_bytes,
    }
    ctx.log(f"counters: {counters}")
    return {"correct": correct, "attempted": done + failed,
            "failed": failed, "e2e": e2e, "counters": counters,
            "checks": checks, "memory_peak_bytes": peak,
            "window_compiles": ctx.compiles.n, "window_s": window_s}


def decode_kernel_bytes(store, plans: dict, ctx) -> float:
    """Bytes the bitunpack launches of the window read and wrote: for
    each request, every bitpacked column its pipeline decodes, of
    every object its plan named (no object was pruned), at the launch
    shape the host adapter pads it to (``bench/cost.py``)."""
    from bench import cost
    from repro.core import format as fmt
    from repro.core import objclass as oc

    headers: dict[str, list] = {}
    total = 0.0
    for names, (ops, count) in plans.items():
        wanted = oc.required_columns(list(ops))
        for name in names:
            if name not in headers:
                headers[name] = fmt.block_header(store.get(name))["columns"]
            total += count * sum(
                cost.bitunpack_bytes(int(np.prod(c["shape"])),
                                     int(c["codec"][len("bitpack"):]))
                for c in headers[name] if c["codec"].startswith("bitpack")
                and (wanted is None or c["name"] in wanted))
    return total
