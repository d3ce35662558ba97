import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: a cell
passes when ``jax.jit(step).lower(**abstract_inputs).compile()`` succeeds
on the production mesh, and its compiled artifact yields the roofline
terms (cost_analysis FLOPs/bytes + collective bytes parsed from the
optimized HLO).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi_9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
  ... --mesh multi       # 2-pod (2,16,16) mesh instead of (16,16)
  ... --variant fused    # packed-ingest train step (perf iteration)
  ... --variant compressed  # int8 cross-pod grad sync (multi mesh only)

Each cell's record lands in results/dryrun/<cell>.json (resume = skip
existing).  NOTE: the XLA_FLAGS line above must execute before any other
jax import in the process — run this module only as __main__.
"""

import argparse
import json
import pathlib
import re
import time
import traceback

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, get_config, registry
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.archs import build_model
from repro.models.inputs import decode_input_specs, train_input_specs
from repro.train.optimizer import OptConfig
from repro.train.steps import abstract_train_state, make_train_step

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / \
    "results" / "dryrun"

# TPU v5e-class constants (assignment)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"= ([^=]*?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS2_RE.search(line)          # iota form [n_groups,group_size]
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)           # explicit {{0,1,..},{..}}
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes by collective kind (ring-algorithm factors).

    all-gather: result is the gathered buffer -> (n-1)/n * result
    all-reduce: result == input -> 2 (n-1)/n * result (RS + AG phases)
    reduce-scatter: result is the shard -> (n-1) * result (input transit)
    all-to-all: (n-1)/n * result ; collective-permute: result
    """
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_seg, kind = m.group(1), m.group(2)
        size = _shape_bytes(result_seg)
        n = max(_group_size(line), 1)
        if kind == "all-gather":
            wire = size * (n - 1) / n
        elif kind == "all-reduce":
            wire = 2.0 * size * (n - 1) / n
        elif kind == "reduce-scatter":
            wire = size * (n - 1)
        elif kind == "all-to-all":
            wire = size * (n - 1) / n
        else:
            wire = float(size)
        out[kind] += wire
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


# --------------------------------------------------------------------------
# cell construction
# --------------------------------------------------------------------------


def _fit_spec(rules: shd.MeshRules, spec: P, shape) -> P:
    """Drop trailing mesh axes from any dim whose size they don't divide
    (e.g. zamba's 32000 vocab over 512-way FSDP -> 32-way)."""
    sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))
    out = []
    for d, entry in enumerate(tuple(spec)):
        if entry is None or d >= len(shape):
            out.append(entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        prod = 1
        for a in axes:
            if shape[d] % (prod * sizes[a]) == 0:
                keep.append(a)
                prod *= sizes[a]
            else:
                break
        out.append(tuple(keep) if len(keep) > 1
                   else (keep[0] if keep else None))
    return P(*out)


def resolve_tree(rules: shd.MeshRules, spec_tree, shapes_tree=None):
    if shapes_tree is None:
        return jax.tree.map(lambda s: rules.named(rules.spec(*tuple(s))),
                            spec_tree, is_leaf=lambda s: isinstance(s, P))
    return jax.tree.map(
        lambda s, x: rules.named(_fit_spec(rules, rules.spec(*tuple(s)),
                                           x.shape)),
        spec_tree, shapes_tree,
        is_leaf=lambda s: isinstance(s, P))


def pick_strategy(cfg, shape, multi_pod: bool) -> str:
    """Parallelism strategy per workload (DESIGN.md §5, sharding.py).

    train: pure FSDP on one pod (1 seq/device — weight-gather collectives
    beat Megatron's activation gathers at these batch sizes); Megatron-SP
    when the pod axis shrinks the per-device batch share (multi-pod) or
    when fp32-moment-free giants need TP'd expert storage (grok).  SSM
    families can't sequence-shard (scans are sequential in S), so multi-
    pod falls back to fsdp_dp.  Serving always runs TP+sequence-sharded
    KV.
    """
    if shape.kind != "train":
        return "tp_sp"
    if cfg.family in ("ssm", "hybrid"):
        return "fsdp_dp" if multi_pod else "fsdp"
    if multi_pod or cfg.name.startswith("grok"):
        return "megatron_sp"
    return "fsdp"


SSM_CHUNK_OVERRIDE: int | None = None


def build_cell(arch: str, shape_name: str, multi_pod: bool, variant: str,
               remat: str, strategy: str | None = None):
    cfg = get_config(arch)
    if SSM_CHUNK_OVERRIDE and cfg.ssm is not None:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, ssm=_dc.replace(
            cfg.ssm, chunk=SSM_CHUNK_OVERRIDE))
    shape = SHAPES[shape_name]
    # perf-iteration variants (EXPERIMENTS.md §Perf):
    #   baseline     scan-flash, f32-cast logits, head_dim TP fallback
    #   flashvjp     custom-vjp FlashAttention backward (it. A1)
    #   optimized    flashvjp + mixed-precision logits dot + padded
    #                head-TP (iterations A2/B2)
    #   fused        optimized + packed-ingest train step
    #   compressed   optimized + int8 cross-pod gradient all-reduce
    from repro.models import attention as _attn
    from repro.models import layers as _layers
    from repro.models import transformer as _tfm
    _attn.FLASH_IMPL = "scan" if variant == "baseline" else "vjp"
    _attn.HEAD_TP = "head_dim" if variant in ("baseline", "flashvjp") \
        else "padded"
    _layers.XENT_MM = "cast" if variant in ("baseline", "flashvjp") \
        else "mixed"
    _tfm.KV_CACHE_QUANT = (variant == "kvint8")  # int8 GQA decode cache
    model = build_model(cfg, remat=remat)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = shd.MeshRules(
        mesh, strategy=strategy or pick_strategy(cfg, shape, multi_pod))

    if shape.kind == "train":
        opt = OptConfig()
        # SSM/hybrid multi-pod: activations scale with the 8-seq/device
        # batch share (scans can't sequence-shard) — grad accumulation
        # over 8 microbatches restores the 1-seq/device footprint.
        micro = 8 if rules.strategy in ("fsdp_dp", "tp_dp") else 1
        step = make_train_step(model, opt, microbatches=micro)
        state_shapes, state_specs = abstract_train_state(
            model, cfg.opt_dtype)
        batch, batch_specs = train_input_specs(cfg, shape)
        if variant == "fused":
            if cfg.frontend != "none":
                raise SystemExit("fused variant needs a token frontend")
            from repro.data.fused_ingest import (
                make_fused_train_step, packed_input_spec)
            step = make_fused_train_step(step)
            batch = packed_input_spec(shape.global_batch, shape.seq_len,
                                      cfg.vocab_size)
            batch_specs = P("dp", None, None)
        elif variant == "compressed":
            if not multi_pod:
                raise SystemExit("compressed variant needs the pod axis")
            from repro.distributed.compression import (
                abstract_compressed_state, make_compressed_train_step)
            step = make_compressed_train_step(model, opt, rules)
            state_shapes, state_specs = abstract_compressed_state(
                state_shapes, state_specs, n_pods=2)
        in_sh = (resolve_tree(rules, state_specs, state_shapes),
                 resolve_tree(rules, batch_specs))
        out_sh = (in_sh[0], None)
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0,))
        args = (state_shapes, batch)
    elif shape.kind == "prefill":
        params_shapes, param_specs = model.abstract()
        batch, batch_specs = train_input_specs(cfg, shape)
        batch = {k: v for k, v in batch.items() if k != "labels"}
        batch_specs = {k: v for k, v in batch_specs.items()
                       if k != "labels"}
        _, cache_specs = model.abstract_cache(
            shape.global_batch, shape.seq_len)
        fn = jax.jit(lambda p, b: model.prefill(p, b),
                     in_shardings=(resolve_tree(rules, param_specs,
                                                params_shapes),
                                   resolve_tree(rules, batch_specs)),
                     out_shardings=(None,
                                    resolve_tree(rules, cache_specs)))
        args = (params_shapes, batch)
    else:  # decode
        params_shapes, param_specs = model.abstract()
        B, S = shape.global_batch, shape.seq_len
        cache, cache_specs = model.abstract_cache(B, S)
        tokens, tok_spec = decode_input_specs(cfg, shape)
        cache_sh = resolve_tree(rules, cache_specs)
        fn = jax.jit(model.decode_step,
                     in_shardings=(resolve_tree(rules, param_specs,
                                                params_shapes),
                                   resolve_tree(rules, tok_spec),
                                   cache_sh),
                     out_shardings=(None, cache_sh),
                     donate_argnums=(2,))
        args = (params_shapes, tokens, cache)

    return cfg, shape, mesh, rules, fn, args


def model_flops(cfg, shape) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # decode: 1 tok/seq


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             variant: str = "baseline", remat: str = "full",
             strategy: str | None = None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "variant": variant, "remat": remat, "ok": False}
    cfg = get_config(arch)
    if shape_name not in cfg.supported_shapes:
        rec.update(skipped=True,
                   reason="long_500k needs sub-quadratic attention")
        return rec
    t0 = time.time()
    cfg, shape, mesh, rules, fn, args = build_cell(
        arch, shape_name, multi_pod, variant, remat, strategy)
    rec["strategy"] = rules.strategy
    with shd.use_rules(rules):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    from repro.launch.hlo_analysis import analyze
    hlo = analyze(compiled.as_text())
    coll = hlo["collective"]
    n_dev = mesh.devices.size

    # scan-aware analyzer terms (XLA's cost_analysis counts while bodies
    # once; keep its raw numbers for reference).  The memory term uses
    # the TPU-order fused-bytes estimate; the count-everything bound is
    # recorded as hlo_bytes_upper.
    flops_dev = float(hlo["flops"])
    bytes_dev = float(hlo["bytes_fused"])
    coll_dev = float(coll["total"])
    mf = model_flops(cfg, shape)

    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    coll_s = coll_dev / ICI_BW
    dom = max(("compute", compute_s), ("memory", memory_s),
              ("collective", coll_s), key=lambda kv: kv[1])[0]

    rec.update(
        ok=True,
        n_devices=int(n_dev),
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            code_bytes=mem.generated_code_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            peak_hbm_bytes=(mem.argument_size_in_bytes
                            + mem.output_size_in_bytes
                            + mem.temp_size_in_bytes
                            - mem.alias_size_in_bytes),
        ),
        hlo_flops_per_dev=flops_dev,
        hlo_bytes_per_dev=bytes_dev,
        hlo_bytes_upper=float(hlo["bytes"]),
        xla_raw_flops=float(cost.get("flops", 0.0)),
        xla_raw_bytes=float(cost.get("bytes accessed", 0.0)),
        collective=coll,
        collective_count=hlo["collective_count"],
        model_flops_total=mf,
        useful_flops_ratio=mf / max(flops_dev * n_dev, 1.0),
        roofline=dict(
            compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
            dominant=dom,
            step_s_bound=max(compute_s, memory_s, coll_s),
            roofline_fraction=compute_s / max(compute_s, memory_s,
                                              coll_s),
        ),
    )
    return rec


def cell_path(rec_or_key) -> pathlib.Path:
    if isinstance(rec_or_key, dict):
        key = (f"{rec_or_key['arch']}.{rec_or_key['shape']}."
               f"{rec_or_key['mesh']}.{rec_or_key['variant']}."
               f"{rec_or_key['remat']}")
    else:
        key = rec_or_key
    return RESULTS_DIR / f"{key}.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--strategy", default=None,
                    help="override the parallelism strategy for the cell")
    ap.add_argument("--ssm-chunk", type=int, default=None,
                    help="override cfg.ssm.chunk (SSD/WKV chunk sweep)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if args.ssm_chunk:
        global SSM_CHUNK_OVERRIDE
        SSM_CHUNK_OVERRIDE = args.ssm_chunk

    archs = [args.arch] if args.arch else list(registry())
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "pod2x16x16" if multi else "pod16x16"
                key = f"{arch}.{shape}.{mesh_name}.{args.variant}." \
                      f"{args.remat}"
                if args.strategy:
                    key += f".{args.strategy}"
                if args.ssm_chunk:
                    key += f".c{args.ssm_chunk}"
                path = cell_path(key)
                if path.exists() and not args.force:
                    print(f"[dryrun] {key}: cached", flush=True)
                    continue
                print(f"[dryrun] {key}: lowering...", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod=multi,
                                   variant=args.variant, remat=args.remat,
                                   strategy=args.strategy)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "variant": args.variant, "remat": args.remat,
                           "ok": False, "error": repr(e)[:1000],
                           "traceback": traceback.format_exc()[-3000:]}
                    failures += 1
                path.write_text(json.dumps(rec, indent=1))
                if rec.get("skipped"):
                    print(f"[dryrun] {key}: SKIP ({rec['reason']})",
                          flush=True)
                elif rec["ok"]:
                    r = rec["roofline"]
                    print(f"[dryrun] {key}: OK compile={rec['compile_s']}s "
                          f"dom={r['dominant']} "
                          f"frac={r['roofline_fraction']:.2f} "
                          f"peak_hbm={rec['memory']['peak_hbm_bytes']/2**30:.2f}GiB",
                          flush=True)
                else:
                    print(f"[dryrun] {key}: FAIL {rec['error'][:200]}",
                          flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
