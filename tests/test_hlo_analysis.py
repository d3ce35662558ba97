"""HLO analyzer cross-checks (run in a subprocess so the 8-device
XLA_FLAGS never leak into other tests' single-device world)."""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.launch.hlo_analysis import analyze

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    sh = lambda *s: NamedSharding(mesh, P(*s))

    # 1. while-free: flops/bytes must match XLA's own cost analysis
    def f(w1, w2, x):
        h = jnp.tanh(x @ w1)
        return (h @ w2).sum()
    comp = jax.jit(f, in_shardings=(sh(None, "model"), sh("model", None),
                                    sh("data", None))).lower(
        jax.ShapeDtypeStruct((512, 1024), jnp.float32),
        jax.ShapeDtypeStruct((1024, 512), jnp.float32),
        jax.ShapeDtypeStruct((256, 512), jnp.float32)).compile()
    got = analyze(comp.as_text())
    ca = comp.cost_analysis()
    assert abs(got["flops"] / ca["flops"] - 1) < 0.05, (got["flops"],
                                                        ca["flops"])
    assert abs(got["bytes"] / ca["bytes accessed"] - 1) < 0.2
    assert got["bytes_fused"] <= got["bytes"]
    assert got["collective"]["all-reduce"] > 0

    # 2. scan: flops must scale with trip count (XLA's count does not)
    L = 12
    def g(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), ()
        h, _ = jax.lax.scan(body, x, None, length=L)
        return h.sum()
    comp2 = jax.jit(g, in_shardings=(sh(None, "model"),
                                     sh("data", None))).lower(
        jax.ShapeDtypeStruct((512, 512), jnp.float32),
        jax.ShapeDtypeStruct((256, 512), jnp.float32)).compile()
    got2 = analyze(comp2.as_text())
    expect = 2 * 128 * 128 * 512 * L
    assert abs(got2["flops"] / expect - 1) < 0.05, (got2["flops"], expect)
    print("HLO_ANALYSIS_OK")
""")


@pytest.mark.slow
def test_analyzer_matches_xla_costs():
    out = subprocess.run([sys.executable, "-c", PROG],
                         capture_output=True, text=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": os.environ.get("HOME", "")},
                         cwd=pathlib.Path(__file__).resolve().parents[1],
                         timeout=600)
    assert "HLO_ANALYSIS_OK" in out.stdout, out.stdout + out.stderr
