"""Query traffic: TPC-H Q6 as one client sends it, closed loop, one
request at a time.

The configuration names the table generator and the store, as a scan
configuration does, and under ``query`` the query and its substitution
ranges (clause 2.4.6.3).  Each request draws its parameters from the
seed, ``(year, discount in hundredths, quantity)``, and runs

    vol.scan(dataset).filter_expr(<the three predicates>)
                     .agg("sum", Col("l_extendedprice") * Col("l_discount"))

through ``Scan.explain`` and ``ScanEngine.execute`` with the client's
object map: one ``filter`` op and one ``agg`` op over a value
expression, evaluated on the OSDs, one partial per OSD
(``exec_combine``).  The discount bounds are the decimals
``(discount +- 1) / 100``.  Keys of a query traffic file:

``keep``        how many answers to keep and check, drawn from the seed
                by reservoir sampling; ``"all"`` keeps every one
``warmup``      ``"request"`` sends one request before the window

The reservoir, the table generator and the count of the kernel's bytes
are the scan driver's.
"""

from __future__ import annotations

import math
import pathlib
import time

import numpy as np

from bench import load_module

BENCH = pathlib.Path(__file__).resolve().parents[1]
SCAN = load_module(BENCH / "drivers" / "scan.py", "bench_driver_scan")


def requests(query: dict, seed: int):
    """Endless stream of Q6 substitution parameters from the seed."""
    if query["name"] != "q6":
        raise ValueError(f"no driver for query {query['name']!r}")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x0606]))
    ranges = [query[k] for k in ("date_years", "discount_hundredths",
                                 "quantity")]
    while True:
        yield tuple(int(rng.integers(lo, hi + 1)) for lo, hi in ranges)


def q6_predicate(req, epoch: np.datetime64):
    """Q6's filter for one request; dates are days since ``epoch``."""
    from repro.core import expr as ex
    year, discount, quantity = req

    def day(y: int) -> int:
        return int((np.datetime64(f"{y}-01-01") - epoch)
                   // np.timedelta64(1, "D"))
    return ex.And((ex.Cmp("l_shipdate", ">=", day(year)),
                   ex.Cmp("l_shipdate", "<", day(year + 1)),
                   ex.Between("l_discount", (discount - 1) / 100,
                              (discount + 1) / 100),
                   ex.Cmp("l_quantity", "<", quantity)))


def q6(vol, dataset: str, req, epoch: np.datetime64):
    """The scan of one Q6 request."""
    from repro.core import expr as ex
    return vol.scan(dataset).filter_expr(q6_predicate(req, epoch)).agg(
        "sum", ex.Col("l_extendedprice") * ex.Col("l_discount"))


def run(ctx) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    # a program without value expressions cannot run the cell: fail
    # here, before the table is built
    from repro.core.expr import Value  # noqa: F401

    from repro.core import (Column, GlobalVOL, LogicalDataset,
                            PartitionPolicy, make_store)
    from repro.kernels.bitunpack import decode_stats

    cfg, traffic, seed = ctx.cfg, ctx.traffic, ctx.args.seed
    gen = SCAN.load_generator(cfg["generator"])
    table = gen.generate(cfg["data"], seed)
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

    stream = requests(cfg["query"], seed)

    def send(req):
        scan = q6(vol, ds.name, req, gen.EPOCH)
        with TraceAnnotation("scan.plan"):
            plan = scan.explain(omap)
        with TraceAnnotation("scan.execute"):
            before = store.fabric.snapshot()
            out, stats = vol.engine.execute(plan, before=before, omap=omap)
        return plan, out, stats

    if traffic.get("warmup") == "request":
        plan, _, _ = send(next(requests(cfg["query"], seed)))
        ctx.log(f"plan: {plan.exec_cls}, "
                f"{[o.name for o in plan.exec_ops]}")
    setup_s = time.perf_counter() - ctx.t0

    keep = traffic.get("keep", "all")
    kept = SCAN.Reservoir(None if keep == "all" else int(keep), seed)
    lat: list[float] = []
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
            req = next(stream)
            t_a = time.perf_counter()
            try:
                plan, out, stats = send(req)
            except Exception as e:  # counted, and the run is not correct
                failed += 1
                ctx.log(f"request failed: {type(e).__name__}: {e}")
                continue
            lat.append(time.perf_counter() - t_a)
            ops, count = plans.get(plan.names, (plan.exec_ops, 0))
            plans[plan.names] = (ops, count + 1)
            if stats["objects_pruned"]:
                kernel_bytes = math.nan  # decoded objects unknown
            kept.offer((req, out))
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
        kernel_bytes = SCAN.decode_kernel_bytes(store, plans, ctx)

    # correctness: every kept answer against the plain reference, and
    # every object's acknowledged write on as many verified replicas
    # as the configuration states
    checks = ctx.ref.check(table, kept.items)
    census = store.copy_census([e.name for e in omap])
    short = sum(1 for c in census.values()
                if len(c["verified"]) < int(st["replicas"]))
    checks["under_replicated_objects"] = {"value": short, "limit": 0}
    correct = failed == 0 and done > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    store.close()

    e2e = {"setup_s": setup_s}
    if done:
        e2e["scan_rows_per_s"] = n * done / window_s
    counters = {
        "requests": done,
        "client_rx_bytes": fab1["client_rx"] - fab0["client_rx"],
        "osd_requests": fab1["ops"] - fab0["ops"],
        "expr_rows": fab1["expr_rows"] - fab0["expr_rows"],
        "decode_calls": dec1["calls"] - dec0["calls"],
        "interpret_calls": dec1["interpret_calls"] - dec0["interpret_calls"],
        "bitunpack_bytes": kernel_bytes,
    }
    if done:
        counters["pass_share"] = counters["expr_rows"] / (n * done)
    ctx.log(f"counters: {counters}")
    return {"correct": correct, "attempted": done + failed,
            "failed": failed, "e2e": e2e, "counters": counters,
            "checks": checks, "memory_peak_bytes": peak,
            "window_compiles": ctx.compiles.n, "window_s": window_s}
