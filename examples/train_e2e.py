"""End-to-end training driver: a ~100M-param LM trained for a few hundred
steps with the entire data/checkpoint path on the object store.

  PYTHONPATH=src python examples/train_e2e.py --preset 100m --steps 300
  PYTHONPATH=src python examples/train_e2e.py --preset 25m  --steps 200
  PYTHONPATH=src python examples/train_e2e.py --preset tiny --steps 40

Everything the paper promises is on: dataset mapped to objects with
planar-bitpacked token columns; loader fetches packed rows with the
zero-decode ``select_packed`` objclass op and hedges stragglers;
the unpack happens inside the compiled step; checkpoints are replicated
objects committed manifest-last; an OSD is killed mid-run and the run
continues; the final restart proves bit-determinism.

Results land in results/train_e2e_<preset>.json.
"""

from __future__ import annotations

import argparse
import json
import pathlib

import numpy as np

from repro.configs.base import ArchConfig
from repro.core import GlobalVOL, make_store
from repro.core.partition import PartitionPolicy
from repro.data.corpus import CorpusSpec, build_corpus
from repro.data.pipeline import ObjectDataLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.models.archs import build_model
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig

PRESETS = {
    # ~103M params: 12L d=768 (gpt2-small-ish, llama-style blocks)
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 head_dim=64, d_ff=2048, vocab_size=32_000,
                 batch=8, seq=256),
    # ~27M params
    "25m": dict(n_layers=8, d_model=448, n_heads=8, n_kv_heads=4,
                head_dim=56, d_ff=1280, vocab_size=16_000,
                batch=8, seq=256),
    "tiny": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                 head_dim=32, d_ff=384, vocab_size=2_000,
                 batch=8, seq=128),
}


def make_cfg(p: dict) -> ArchConfig:
    import jax.numpy as jnp
    return ArchConfig(
        name="train_e2e", family="dense",
        n_layers=p["n_layers"], d_model=p["d_model"],
        n_heads=p["n_heads"], n_kv_heads=p["n_kv_heads"],
        head_dim=p["head_dim"], d_ff=p["d_ff"],
        vocab_size=p["vocab_size"],
        param_dtype=jnp.float32, compute_dtype=jnp.float32)


def build_store(p: dict, cfg: ArchConfig, steps: int, seed: int):
    """An 8-OSD store holding the bitpacked token corpus (~4 epochs of
    ``steps``) mapped to objects through the VOL."""
    store = make_store(8, replicas=2)
    vol = GlobalVOL(store)
    n_seqs = max(steps * p["batch"] // 4, 512)  # ~4 epochs
    build_corpus(vol, CorpusSpec(n_seqs=n_seqs, seq_len=p["seq"],
                                 vocab_size=cfg.vocab_size, seed=seed),
                 policy=PartitionPolicy(target_object_bytes=2 << 20,
                                        max_object_bytes=16 << 20))
    print(f"[e2e] corpus: {n_seqs} x {p['seq']} tokens in "
          f"{store.stats()['n_objects']} objects")
    return store, vol


def make_trainer(p: dict, cfg: ArchConfig, store, vol, steps: int,
                 seed: int, *, ckpt_every: int | None = None):
    """The packed-ingest Trainer over the store's corpus: the loader
    ships packed words (zero-decode ``select_packed``) and the unpack
    runs inside the compiled step.  Returns (trainer, loader)."""
    model = build_model(cfg, remat="none")
    loader = ObjectDataLoader(vol, "corpus", global_batch=p["batch"],
                              seed=seed, packed=True, prefetch=2,
                              hedge_timeout_s=0.5)
    trainer = Trainer(
        model, loader, store,
        opt=OptConfig(lr=6e-4, warmup_steps=max(steps // 20, 5),
                      total_steps=steps),
        cfg=TrainerConfig(total_steps=steps,
                          ckpt_every=ckpt_every or max(steps // 4, 10),
                          log_every=max(steps // 20, 5),
                          packed_ingest=True))
    return trainer, loader


def kill_and_recover(store, step: int) -> dict:
    """Fail-stop the first up OSD and re-replicate from the survivors."""
    victim = store.cluster.up_osds[0]
    store.fail_osd(victim)
    rec = store.recover()
    print(f"[e2e] step {step}: killed {victim}; recovery moved "
          f"{rec['objects_moved']} replicas, lost "
          f"{rec['objects_lost']}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--kill-osd-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    p = PRESETS[args.preset]

    cfg = make_cfg(p)
    print(f"[e2e] {args.preset}: {cfg.param_count() / 1e6:.1f}M params")

    store, vol = build_store(p, cfg, args.steps, args.seed)
    trainer, loader = make_trainer(p, cfg, store, vol, args.steps,
                                   args.seed)
    kill_at = args.kill_osd_at or args.steps // 2

    path = pathlib.Path(__file__).resolve().parents[1] / "results"
    path.mkdir(exist_ok=True)
    out_file = path / f"train_e2e_{args.preset}.json"

    def write_partial(history) -> None:
        losses = [h["loss"] for h in history]
        out_file.write_text(json.dumps({
            "preset": args.preset, "params_m": cfg.param_count() / 1e6,
            "steps_done": len(losses), "steps_target": args.steps,
            "loss_first": losses[0], "loss_last": losses[-1],
            "loss_curve": losses[:: max(len(losses) // 50, 1)],
            "wall_s_per_step": float(np.mean(
                [h["wall_s"] for h in history[2:]] or [0.0])),
        }, indent=1))

    def on_step(step: int) -> None:
        if step == kill_at:
            kill_and_recover(store, step)
        if step % 10 == 0:
            write_partial(trainer.history)

    trainer.run(on_step=on_step)
    loader.close()

    losses = [h["loss"] for h in trainer.history]
    out = {
        "preset": args.preset,
        "params_m": cfg.param_count() / 1e6,
        "steps": args.steps,
        "loss_first": losses[0], "loss_last": losses[-1],
        "loss_curve": losses[:: max(len(losses) // 50, 1)],
        "stragglers_flagged": trainer.straggler.flagged,
        "store": store.stats()["fabric"],
        "wall_s_per_step": float(np.mean(
            [h["wall_s"] for h in trainer.history[2:]])),
    }
    out_file.write_text(json.dumps(out, indent=1))
    print(f"[e2e] loss {out['loss_first']:.3f} -> {out['loss_last']:.3f} "
          f"over {args.steps} steps "
          f"({out['wall_s_per_step'] * 1e3:.0f} ms/step); "
          f"results -> results/train_e2e_{args.preset}.json")
    assert out["loss_last"] < out["loss_first"], "training must learn"


if __name__ == "__main__":
    main()
