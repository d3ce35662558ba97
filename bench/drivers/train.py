"""Training traffic: one job, ``Trainer.run`` fed from the store.

The configuration gives the model (``model``), the job (``train``:
sequence length, sequences a step, optimizer, precision) and where the
corpus lives (``corpus``, ``store``).  The traffic file gives the input
path (``loader``: packed words or decoded rows, prefetch depth) and how
many first steps the reference follows (``checked_steps``).

Set-up writes the corpus through ``GlobalVOL``, builds the state on the
device in one jitted call from the seed, and drives the Trainer through
its first ``checked_steps`` steps: the same compiled step, loader and
state that the window then runs on, so those steps are the warm-up.
The window is ``Trainer.run`` on from there, ended through ``on_step``
at the first step that completes after ``--seconds``.  Spans:
``loader.next`` around each batch the Trainer takes, ``train.step``
around each call of its jitted step (the dispatch).
"""

from __future__ import annotations

import gc
import time

import numpy as np

WINDOW_STEPS = 10 ** 9


class StopWindow(Exception):
    """Raised from ``on_step`` to end the measured window."""


class SpannedLoader:
    """The loader the Trainer is given: the program's loader, with a
    ``loader.next`` span and a host-clock wait around every batch."""

    def __init__(self, inner):
        self.inner = inner
        self.waits: list[float] = []

    def seek(self, step: int) -> None:
        self.inner.seek(step)

    def __iter__(self):
        return self

    def __next__(self):
        from jax.profiler import TraceAnnotation
        t = time.perf_counter()
        with TraceAnnotation("loader.next"):
            batch = next(self.inner)
        self.waits.append(time.perf_counter() - t)
        return batch


def spanned(step_fn):
    from jax.profiler import TraceAnnotation

    def call(state, batch):
        with TraceAnnotation("train.step"):
            return step_fn(state, batch)
    return call


def arch_config(cfg: dict):
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig
    m, prec = cfg["model"], cfg["precision"]
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        act="silu_gated", norm="rmsnorm", rope_theta=m["rope_theta"],
        param_dtype=jnp.dtype(prec["params"]),
        compute_dtype=jnp.dtype(prec["compute"]),
        opt_dtype=jnp.dtype(prec["optimizer_state"]))


def _name(path) -> str:
    return ".".join(str(getattr(k, "key", k)) for k in path)


def param_builder(model):
    """key -> the Trainer's parameter tree: every leaf from
    ``bench/gen/lm_weights``, stacked layers drawn layer by layer under
    their own names, in the types the model serves them in."""
    import jax
    import jax.numpy as jnp

    from bench.gen import lm_weights
    shapes, _ = model.abstract()

    def build(key):
        def make(path, sds):
            name = _name(path)
            if name.startswith("blocks."):
                rest = name[len("blocks."):]
                return jnp.stack([lm_weights.leaf(
                    key, f"blocks.{i}.{rest}", sds.shape[1:], sds.dtype)
                    for i in range(sds.shape[0])])
            return lm_weights.leaf(key, name, sds.shape, sds.dtype)
        return jax.tree_util.tree_map_with_path(make, shapes)
    return build


def program_state(model, seed: int, opt_dtype):
    """The Trainer's state, built on the device in one jitted call from
    the seed: the parameters, optimizer moments at zero."""
    import jax

    from bench.gen import lm_weights
    from repro.train.optimizer import init_opt_state
    build = param_builder(model)

    def state(key):
        params = build(key)
        return {"params": params, "opt": init_opt_state(params, opt_dtype)}
    return jax.jit(state)(lm_weights.base_key(seed))


def _leaf_norms(tree) -> dict:
    """Norm of every leaf (traced), stacked layers split into their own
    leaves (``blocks.<i>.<rest>``), as the reference names them."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        name, x = _name(path), x.astype(jnp.float32)
        if name.startswith("blocks."):
            per = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[f"blocks.{i}.{name[len('blocks.'):]}"] = per[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def leaf_norms(tree) -> dict[str, float]:
    import jax
    return {k: float(v) for k, v in jax.jit(_leaf_norms)(tree).items()}


def change_norms(model, params, seed: int) -> dict[str, float]:
    """Per-leaf norm of the parameters' change from their initial
    values, which are drawn again from the seed inside the same call."""
    import jax

    from bench.gen import lm_weights
    build = param_builder(model)

    def norms(p, key):
        p0 = build(key)
        return _leaf_norms(jax.tree.map(
            lambda x, y: x.astype(np.float32) - y.astype(np.float32), p, p0))
    got = jax.jit(norms)(params, lm_weights.base_key(seed))
    return {k: float(v) for k, v in got.items()}


def run(ctx) -> dict:
    import jax
    from jax.profiler import TraceAnnotation

    from bench import cost
    from bench.gen import lm_corpus
    from repro.core import (Column, GlobalVOL, LogicalDataset,
                            PartitionPolicy, make_store)
    from repro.data.pipeline import ObjectDataLoader
    from repro.models.archs import build_model
    from repro.train.optimizer import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg, traffic, seed = ctx.cfg, ctx.traffic, ctx.args.seed
    m, tr, cp, st = cfg["model"], cfg["train"], cfg["corpus"], cfg["store"]
    seq, batch = int(tr["seq_len"]), int(tr["batch"])
    corpus = lm_corpus.generate(cp, seq, m["vocab_size"], seed)
    n_seqs = len(corpus["tokens"])
    store = make_store(int(st["osds"]), replicas=int(st["replicas"]))
    vol = GlobalVOL(store)
    ds = LogicalDataset("corpus", (Column("tokens", "int32", (seq,)),
                                   Column("doc_id", "int32"),
                                   Column("quality", "float32")),
                        n_rows=n_seqs, unit_rows=int(cp["unit_rows"]))
    omap = vol.create(ds, PartitionPolicy(
        target_object_bytes=int(st["object_bytes"]),
        max_object_bytes=int(st["max_object_bytes"])))
    vol.write(omap, corpus)
    ctx.log(f"corpus: {n_seqs} x {seq} tokens in {omap.n_objects} "
            f"objects, {store.cluster.replicas} replicas")

    model = build_model(arch_config(cfg), remat=tr["remat"])
    ld = traffic["loader"]
    loader = SpannedLoader(ObjectDataLoader(
        vol, "corpus", global_batch=batch, seed=seed,
        packed=bool(ld["packed"]), prefetch=int(ld["prefetch"]),
        window_steps=int(ld.get("window_steps", 1))))
    o = tr["optimizer"]
    trainer = Trainer(
        model, loader, store,
        opt=OptConfig(lr=o["lr"], betas=tuple(o["betas"]), eps=o["eps"],
                      weight_decay=o["weight_decay"],
                      clip_norm=o["clip_norm"],
                      warmup_steps=o["warmup_steps"],
                      total_steps=o["total_steps"],
                      min_lr_frac=o["min_lr_frac"]),
        cfg=TrainerConfig(total_steps=1, ckpt_every=WINDOW_STEPS,
                          log_every=WINDOW_STEPS,
                          packed_ingest=bool(ld["packed"])),
        log=lambda _msg: None)
    trainer.train_step = spanned(trainer.train_step)

    # set-up: the first steps, through the window's own step and feed
    checked = int(traffic["checked_steps"])
    state = program_state(model, seed, model.cfg.opt_dtype)
    state = trainer.run(state=state, start_step=0)
    b1 = o["betas"][0]
    gnorm = trainer.history[0]["grad_norm"]
    clip = min(1.0, o["clip_norm"] / max(gnorm, 1e-9))
    prog = {"grad_norms": {k: v / ((1 - b1) * clip) for k, v
                           in leaf_norms(state["opt"]["m"]).items()}}
    trainer.cfg.total_steps = checked
    state = trainer.run(state=state, start_step=1)
    prog["change_norms"] = change_norms(model, state["params"], seed)
    prog["losses"] = [h["loss"] for h in trainer.history[:checked]]
    setup_s = time.perf_counter() - ctx.t0

    steps = 0
    t_begin = 0.0
    waits0 = len(loader.waits)

    def on_step(_step: int) -> None:
        nonlocal steps
        steps += 1
        if time.perf_counter() - t_begin >= ctx.args.seconds:
            raise StopWindow

    trainer.cfg.total_steps = WINDOW_STEPS
    if ctx.trace_dir is not None:
        jax.profiler.start_trace(str(ctx.trace_dir))
    ctx.compiles.on = True
    failed = 0
    t_begin = time.perf_counter()
    with TraceAnnotation("window"):
        try:
            trainer.run(state=state, start_step=checked, on_step=on_step)
        except StopWindow:
            pass
        except Exception as e:  # the step that raised is the failure
            failed = 1
            ctx.log(f"step failed: {type(e).__name__}: {e}")
    t_end = time.perf_counter()
    ctx.compiles.on = False
    if ctx.trace_dir is not None:
        jax.profiler.stop_trace()
    window_s = t_end - t_begin
    peak = ctx.memory_peak()
    losses = [h["loss"] for h in trainer.history[checked:]]
    waits = loader.waits[waits0:]
    step_ms = float(np.median([h["wall_s"] for h in
                               trainer.history[checked:]] or [0.0])) * 1e3
    ctx.log(f"window {window_s:.3f} s: {steps} steps of {batch} x {seq} "
            f"tokens; step {step_ms:.1f} ms median; "
            f"batch wait {np.median(waits) * 1e3 if waits else 0:.3f} ms "
            f"median; losses {prog['losses']} then "
            f"{losses[:1]}..{losses[-1:]}")

    # free the program's state before the reference takes the chip
    del state
    loader.inner.close()
    census = store.copy_census([e.name for e in omap])
    short = sum(1 for c in census.values()
                if len(c["verified"]) < int(st["replicas"]))
    store.close()
    del trainer, model, loader, store, vol
    gc.collect()

    batches = [corpus["tokens"][lm_corpus.rows_for_step(seed, s, n_seqs,
                                                        batch)]
               for s in range(checked)]
    t_ref = time.perf_counter()
    ref = ctx.ref.first_steps(cfg, seed, batches)
    ctx.log(f"reference: {time.perf_counter() - t_ref:.1f} s; losses "
            f"{ref['losses']}")
    ctx.log(f"readings: {ctx.ref.gaps(prog, ref)}")
    checks = ctx.ref.check(prog, ref)
    checks["under_replicated_objects"] = {"value": short, "limit": 0}
    finite = bool(np.all(np.isfinite(losses)))
    correct = failed == 0 and steps > 0 and finite and all(
        c["value"] <= c["limit"] for c in checks.values())

    tokens = steps * batch * seq
    counters = {"steps": steps, "tokens": tokens,
                "flops_per_token": cost.lm_train_flops_per_token(m, seq)}
    return {"correct": correct, "attempted": steps + failed,
            "failed": failed,
            "e2e": {"setup_s": setup_s,
                    "train_tokens_per_s": tokens / window_s},
            "counters": counters, "checks": checks,
            "memory_peak_bytes": peak, "window_compiles": ctx.compiles.n,
            "window_s": window_s}
