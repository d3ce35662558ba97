"""Serving launcher: ``python -m repro.launch.serve --arch yi_9b --smoke``.

Boots the engine with random weights (or a checkpoint from the store via
--restore), serves synthetic batched requests, and parks the session's
KV pages to the object store.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core import make_store
from repro.launch.compile_cache import enable_compile_cache
from repro.models.archs import build_model
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.frontend != "none":
        raise SystemExit(f"{cfg.name}: frontend-stub archs decode over "
                         "token ids after a stubbed prefill; use the "
                         "dryrun for their serve-step lowering")
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(args.seed))
    store = make_store(4, replicas=2)
    engine = ServeEngine(model, params, max_seq=args.max_seq, store=store)

    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=rng.integers(
        1, cfg.vocab_size, int(rng.integers(4, 17))).astype(np.int32),
        max_new=args.max_new) for _ in range(args.batch)]
    t0 = time.perf_counter()
    comps = engine.generate(reqs)
    dt = time.perf_counter() - t0
    toks = sum(c.steps for c in comps)
    print(f"[serve] {args.batch} reqs, {toks} tokens, "
          f"{dt * 1e3:.0f} ms ({toks / dt:.1f} tok/s)")
    engine.park_session("session-0")
    print(f"[serve] parked KV pages: "
          f"{len(store.list_objects('kv/'))} objects")


if __name__ == "__main__":
    main()
