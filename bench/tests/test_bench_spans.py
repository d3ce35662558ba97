"""The program's own spans on the CPU, under the JAX profiler: a tiny
bitpacked table scanned once table-out and once as an aggregate, with
the bitunpack kernel forced on (interpret mode), and a loader fetch.
The trace is reduced as the harness reduces it; each span must be
there, nested as the layers nest, and each reader of a program span
must read a value from it."""

from __future__ import annotations

import math
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import load_module, run, trace_reduce as tr  # noqa: E402

SCAN_SPANS = ("front.compile", "front.request", "front.assemble",
              "store.request", "osd.serve", "osd.verify", "osd.decode",
              "osd.apply", "osd.encode", "codec.bitunpack")
# (inner, outer): every ``inner`` span lies inside an ``outer`` one on
# the same thread
NESTING = (("store.request", "front.request"),
           ("osd.serve", "store.request"),
           ("osd.verify", "osd.serve"), ("osd.decode", "osd.serve"),
           ("osd.apply", "osd.serve"), ("osd.encode", "osd.serve"),
           ("codec.bitunpack", "osd.decode"),
           ("front.assemble", "front.request"))
# sibling spans of one thread that must never overlap: the client
# assembles a frame only once the store's round trip has closed
DISJOINT = (("front.assemble", "store.request"),
            ("osd.verify", "osd.decode"), ("osd.decode", "osd.apply"),
            ("osd.apply", "osd.encode"), ("osd.verify", "osd.encode"))
CASES = ("concat", "combine")  # exec_concat (table out), exec_combine
SPAN_READERS = ("compile_ms", "assemble_ms_per_scan", "osd_ms_per_scan",
                "verify_ms_per_scan", "decode_ms_per_scan",
                "bitunpack_host_ms_per_scan", "loader_wait_ms",
                "produce_ms")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    """Planes of one profiler session: a ``case.<name>`` marker around
    each scan and around the loader's first two batches, all inside the
    ``window`` span the reduction needs."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.core import (Column, GlobalVOL, LogicalDataset,
                            PartitionPolicy, make_store)
    from repro.core import format as fmt
    from repro.data.pipeline import ObjectDataLoader

    rng = np.random.default_rng(7)
    n, seq = 2048, 64
    table = {"k": rng.integers(0, 1000, n).astype(np.int32),   # bitpack10
             "q": rng.integers(1, 50, n).astype(np.int32),     # bitpack6
             "price": rng.random(n)}
    store = make_store(3, replicas=2)
    vol = GlobalVOL(store)
    ds = LogicalDataset("t", tuple(Column(k, v.dtype.str)
                                   for k, v in table.items()),
                        n_rows=n, unit_rows=512)
    vol.write(vol.create(ds, PartitionPolicy(target_object_bytes=6000)),
              table)
    corpus = LogicalDataset("corpus", (Column("tokens", "int32", (seq,)),),
                            n_rows=32, unit_rows=8)
    vol.write(vol.create(corpus, PartitionPolicy(target_object_bytes=4096)),
              {"tokens": rng.integers(0, 500, (32, seq)).astype(np.int32)})

    out = tmp_path_factory.mktemp("trace")
    fmt.set_bitunpack_backend("device")
    try:
        jax.profiler.start_trace(str(out))
        try:
            with TraceAnnotation("window"):
                with TraceAnnotation("case.concat"):
                    vol.scan("t").filter("price", ">", 0.5).execute()
                with TraceAnnotation("case.combine"):
                    vol.scan("t").filter("price", ">", 0.5) \
                        .agg("sum", "q").agg("max", "k").execute()
                loader = ObjectDataLoader(vol, "corpus", global_batch=4,
                                          prefetch=1)
                with TraceAnnotation("case.loader"):
                    next(loader)
                    next(loader)
                loader.close()
        finally:
            jax.profiler.stop_trace()
    finally:
        fmt.set_bitunpack_backend("auto")
        store.close()
    planes = tr.load(out)
    # the request identifiers ride as event stats, which ``load`` drops
    pd = ProfileData.from_file(str(next(out.rglob("*.xplane.pb"))))
    meta = [(e.name, dict(e.stats)) for p in pd.planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name.startswith("loader.")]
    return planes, meta


def _lines(planes):
    """Host spans as ``{name: [(start, end), ...]}``, one dict a line."""
    out = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                spans: dict = {}
                for name, s, d in ln["events"]:
                    spans.setdefault(name, []).append((s, s + d))
                out.append(spans)
    return out


def _case(planes, case: str) -> dict:
    """The spans inside the ``case.<case>`` marker, on its line."""
    for spans in _lines(planes):
        if f"case.{case}" in spans:
            (lo, hi), = spans[f"case.{case}"]
            return {k: [(s, e) for s, e in v if s >= lo and e <= hi]
                    for k, v in spans.items()}
    raise AssertionError(f"no case.{case} marker")


@pytest.mark.parametrize("case", CASES)
def test_a_scan_opens_every_scan_span(trace, case):
    spans = _case(trace[0], case)
    assert {k for k, v in spans.items() if v} >= set(SCAN_SPANS)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("inner,outer", NESTING)
def test_scan_spans_nest_as_the_layers_do(trace, case, inner, outer):
    spans = _case(trace[0], case)
    for s, e in spans[inner]:
        assert any(a <= s and e <= b for a, b in spans[outer]), \
            (inner, outer, s, e)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("a,b", DISJOINT)
def test_sibling_spans_do_not_overlap(trace, case, a, b):
    spans = _case(trace[0], case)
    for s, e in spans[a]:
        assert not any(s < f and t < e for t, f in spans[b]), (a, b, s, e)


def test_a_loader_batch_is_produced_and_waited_for_under_one_step(trace):
    planes, meta = trace
    waits = _case(planes, "loader")["loader.wait"]
    assert len(waits) == 2
    steps = {name: sorted(int(m["step"]) for n, m in meta if n == name)
             for name in ("loader.produce", "loader.wait")}
    assert steps["loader.wait"] == [0, 1]
    assert set(steps["loader.wait"]) <= set(steps["loader.produce"])
    # the producer runs on its own thread, and the store's spans
    # open under its span there
    producer = [s for s in _lines(planes) if "loader.produce" in s]
    assert producer and all("case.loader" not in s for s in producer)
    assert any("store.request" in s for s in producer)


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_each_span_reader_reads_the_trace(trace, metric):
    red = tr.reduce(trace[0])
    read = load_module(run.reader_file(metric, ROOT),
                       f"bench_metric_{metric}").read
    val = read({"trace": red, "counters": {"requests": 2}})
    assert val is not None and math.isfinite(val) and val > 0, metric
    # no span, no reading, as on a program that opens none
    assert read({"trace": dict(red, spans={}),
                 "counters": {"requests": 2}}) is None
