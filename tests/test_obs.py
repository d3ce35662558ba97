"""``repro.obs.span``: a profiler span that costs nothing to import."""

from __future__ import annotations

import contextlib
import os
import pathlib
import subprocess
import sys

import pytest

from repro import obs

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def test_span_is_a_null_context_while_jax_is_not_loaded(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    s = obs.span("front.request", req=1)
    assert isinstance(s, contextlib.nullcontext)
    assert s is obs.span("osd.serve")  # one shared context
    with s:
        pass


@pytest.mark.parametrize("module", ["repro.obs", "repro.core",
                                    "repro.data.pipeline"])
def test_importing_the_store_does_not_load_jax(module):
    code = (f"import sys, {module}; "
            "assert 'jax' not in sys.modules, 'jax was imported'")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


@pytest.mark.parametrize("jax_loaded", [True, False])
def test_a_span_whose_body_raises_still_closes(jax_loaded, monkeypatch,
                                               tmp_path):
    import jax
    from jax.profiler import ProfileData

    if not jax_loaded:
        monkeypatch.delitem(sys.modules, "jax")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(ValueError, match="inside"):
            with obs.span("obs.test", req=7):
                raise ValueError("inside")
        with obs.span("obs.after"):
            pass
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    events = {e.name: e for p in pd.planes for ln in p.lines
              for e in ln.events}
    if jax_loaded:
        raised, after = events["obs.test"], events["obs.after"]
        assert raised.duration_ns >= 0
        # closed where it raised: the next span starts after it ends
        assert raised.start_ns + raised.duration_ns <= after.start_ns
    else:
        assert "obs.test" not in events and "obs.after" not in events


@pytest.mark.parametrize("operand", ["column", "expression"])
def test_osd_expr_opens_once_per_object_that_evaluates_an_operand(
        operand, tmp_path):
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from repro.core import (Column, GlobalVOL, LogicalDataset,
                            PartitionPolicy, make_store)
    from repro.core import expr as ex

    rng = np.random.default_rng(3)
    table = {"p": rng.random(2000), "d": rng.random(2000)}
    store = make_store(3, replicas=2)
    vol = GlobalVOL(store)
    ds = LogicalDataset("t", tuple(Column(k, v.dtype.str)
                                   for k, v in table.items()),
                        n_rows=2000, unit_rows=250)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=8000))
    vol.write(omap, table)
    agg = ex.Col("p") * ex.Col("d") if operand == "expression" else "p"
    jax.profiler.start_trace(str(tmp_path))
    try:
        vol.scan("t").filter("d", "<", 0.5).agg("sum", agg).execute()
    finally:
        jax.profiler.stop_trace()
        store.close()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    names = [e.name for p in pd.planes for ln in p.lines for e in ln.events]
    want = omap.n_objects if operand == "expression" else 0
    assert omap.n_objects > 1
    assert names.count("osd.expr") == want
    assert names.count("osd.apply") == omap.n_objects


def test_osd_verify_carries_the_bytes_it_checked(tmp_path):
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from repro.core import (Column, GlobalVOL, LogicalDataset,
                            PartitionPolicy, make_store)

    rng = np.random.default_rng(4)
    table = {"p": rng.random(2000), "d": rng.random(2000),
             "q": rng.integers(0, 50, 2000)}
    store = make_store(3, replicas=2)
    vol = GlobalVOL(store)
    ds = LogicalDataset("t", tuple(Column(k, v.dtype.str)
                                   for k, v in table.items()),
                        n_rows=2000, unit_rows=250)
    omap = vol.create(ds, PartitionPolicy(target_object_bytes=8000))
    vol.write(omap, table)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, stats = vol.scan("t").filter("d", "<", 0.5).agg(
            "sum", "q").execute()
    finally:
        jax.profiler.stop_trace()
        store.close()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    checked = [dict(e.stats)["bytes"] for p in pd.planes for ln in p.lines
               for e in ln.events if e.name == "osd.verify"]
    assert len(checked) == omap.n_objects > 1
    assert sum(checked) == stats["verify_bytes"] > 0
