"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout
root.  Its configuration file (``bench/configs/<config>.json``) and its
traffic file (``bench/traffic/<traffic>.json``) say what to build and
what to send; the traffic's ``kind`` picks the driver in
``bench/drivers/``.  The driver builds the data from ``--seed``, warms
up every shape the traffic uses (set-up), measures for ``--seconds``,
then checks what the timed path produced against the plain reference
beside the configuration.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the JAX profiler and
the metrics are the cell's per-layer metrics, each read by its own
reader in ``bench/metrics/<metric>.py``; a metric split by cell
(``device_idle.train``) without a file of its own is read by the file
of its stem (``device_idle.py``).  The last line of stdout is
one JSON object; the numbers that decided ``correct`` are printed, each
beside its limit, as the last lines of stderr and under the result's
last key, ``checks``.

The run needs a TPU with at least the chips the cell asks for; without
one it exits non-zero and prints no result.  JAX's persistent
compilation cache is kept at ``.jax_cache/`` inside the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, spec: dict, root: pathlib.Path = ROOT):
    """(workload, config entry, config file, traffic file) of a cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    work = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[work["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{work['traffic']}.json").read_text())
    return work, conf, cfg, traffic


def metrics_for(cell: str, entries: list[dict]) -> list[dict]:
    """The metrics of ``entries`` that this cell reports."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def reader_file(metric: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    """The reader of a per-layer metric: ``bench/metrics/<metric>.py``,
    or else that of the name with its last ``.part`` taken off, and so
    on, so that the parts of one quantity split by cell share one."""
    name = metric
    while True:
        path = root / "bench" / "metrics" / f"{name}.py"
        if path.is_file() or "." not in name:
            return path
        name = name.rsplit(".", 1)[0]


def device_info(chips: int, allow_cpu: bool) -> dict:
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if not allow_cpu and (plat == "cpu" or len(devs) < chips):
        raise NoDevice(f"want {chips} accelerator chip(s); JAX sees "
                       f"{len(devs)} {plat} device(s)")
    return {"platform": plat, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA backend compiles (cache loads included) while on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event == self.EVENT:
            self.n += 1


def main(argv: list[str] | None = None, *, allow_cpu: bool = False,
         root: pathlib.Path = ROOT, config_override: dict | None = None,
         t_start: float | None = None) -> dict:
    """Run one cell and return its result object (also printed).
    ``allow_cpu`` and ``config_override`` serve the CPU tests: the first
    skips the look for a chip, the second replaces sizes in the
    configuration (a tiny table, a tiny model)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = T_START if t_start is None else t_start

    spec = load_spec(root)
    work, _, cfg, traffic = load_cell(args.workload, spec, root)
    if config_override:
        cfg = _merge(cfg, config_override)
    device = device_info(int(work["chips"]), allow_cpu)

    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import load_module, trace_reduce
    driver = load_module(BENCH / "drivers" / f"{traffic['kind']}.py",
                         f"bench_driver_{traffic['kind']}")
    ref = load_module(BENCH / "configs" / f"{work['config']}_ref.py",
                      f"bench_ref_{work['config']}")

    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR / f"{args.workload}.{args.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    counter = CompileCounter()
    ctx = Context(args=args, work=work, cfg=cfg, traffic=traffic, ref=ref,
                  t0=t0, trace_dir=trace_dir, compiles=counter,
                  memory_peak=memory_peak_bytes)
    obs = driver.run(ctx)

    result: dict = {"correct": bool(obs["correct"]),
                    "attempted": int(obs["attempted"]),
                    "failed": int(obs["failed"]), "metrics": {}}
    device["memory_peak_bytes"] = obs.get("memory_peak_bytes")
    if args.trace:
        red = trace_reduce.reduce(trace_reduce.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs["trace"] = red
        obs["peaks"] = _peaks(device["kind"], allow_cpu)
        for m in metrics_for(args.workload, spec["per_layer"]):
            reader = load_module(reader_file(m["name"], root),
                                 f"bench_metric_{m['name']}")
            val = reader.read(obs)
            if val is not None:
                result["metrics"][m["name"]] = {"value": float(val),
                                                "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": red["top_gaps"]}
    else:
        for m in metrics_for(args.workload, spec["end_to_end"]):
            if m["name"] in obs["e2e"]:
                result["metrics"][m["name"]] = {
                    "value": float(obs["e2e"][m["name"]]),
                    "unit": m["unit"]}
        result["device"] = device
    result["checks"] = obs["checks"]

    print(f"[bench] compiles inside the window: {obs['window_compiles']}",
          file=sys.stderr)
    for name, c in obs["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


class Context:
    """What a driver gets: the parsed arguments, the cell, its
    configuration and traffic, its reference module, the process start
    time, where to put a trace, the compile counter and the device
    memory reader."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _peaks(kind: str, allow_cpu: bool) -> dict | None:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind in table:
        return table[kind]
    if allow_cpu:
        return None
    raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def cli() -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program of a cell, however quick to compile, is kept, so a
    # later run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        main()
    except NoDevice as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(cli())
