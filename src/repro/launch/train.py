"""Training launcher: ``python -m repro.launch.train --arch yi_9b --smoke``.

Stands up the object store, ingests a synthetic corpus through the VOL,
and runs the Trainer (object-store data path, packed ingest, checkpoint/
restart).  ``--smoke`` selects the reduced config — the full configs are
exercised via ``repro.launch.dryrun``.
"""

from __future__ import annotations

import argparse

from repro.configs.base import get_config
from repro.core import GlobalVOL, make_store
from repro.core.partition import PartitionPolicy
from repro.data.corpus import CorpusSpec, build_corpus
from repro.data.pipeline import ObjectDataLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.models.archs import build_model
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--packed", action="store_true", default=True)
    ap.add_argument("--no-packed", dest="packed", action="store_false")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-osds", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if not args.smoke:
        print("WARNING: full config on CPU — expect extreme slowness; "
              "use --smoke or the dryrun for full configs")
    if cfg.frontend != "none" and args.packed:
        print(f"[train] {cfg.name}: frontend stub takes embeddings — "
              "disabling packed ingest")
        args.packed = False
    seq = args.seq or (2 * cfg.ssm.chunk if cfg.ssm is not None
                       and cfg.ssm.chunk <= 64 else 128)

    store = make_store(args.n_osds, replicas=2)
    vol = GlobalVOL(store)
    build_corpus(vol, CorpusSpec(
        n_seqs=max(args.steps * args.global_batch // 2, 256),
        seq_len=seq, vocab_size=cfg.vocab_size, seed=args.seed),
        policy=PartitionPolicy(target_object_bytes=2 << 20,
                               max_object_bytes=16 << 20))

    model = build_model(cfg, remat="none")
    if cfg.frontend != "none":
        raise SystemExit(f"{cfg.name}: modality-frontend archs train via "
                         "examples/train_e2e-style embedding stubs; use a "
                         "token arch here")
    loader = ObjectDataLoader(vol, "corpus", global_batch=args.global_batch,
                              seed=args.seed, packed=args.packed,
                              prefetch=2)
    trainer = Trainer(
        model, loader, store,
        opt=OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 2),
                      total_steps=args.steps),
        cfg=TrainerConfig(total_steps=args.steps,
                          ckpt_every=args.ckpt_every,
                          log_every=max(args.steps // 10, 1),
                          packed_ingest=args.packed))
    trainer.run()
    loader.close()
    print(f"[train] done: loss {trainer.history[0]['loss']:.3f} -> "
          f"{trainer.history[-1]['loss']:.3f}; "
          f"ckpts: {len(store.list_objects('ckpt/'))} objects")


if __name__ == "__main__":
    main()
