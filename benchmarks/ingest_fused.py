"""Paper §2 goal 2 (codec offload) on the training input path.

Compares the bytes entering the device program for one train step:
  plain  — tokens + labels as int32
  fused  — planar-bitpacked words, unpacked + labels derived in-step

and times the host-side loader fetch for both (the packed path also
skips OSD-side decode via select_packed).  The in-graph unpack cost and
the argument-bytes reduction are read from the compiled step.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import GlobalVOL, make_store
from repro.core.partition import PartitionPolicy
from repro.data.corpus import CorpusSpec, build_corpus
from repro.data.fused_ingest import device_stream, make_fused_train_step
from repro.data.pipeline import ObjectDataLoader
from repro.models.archs import build_model
from repro.train.optimizer import OptConfig
from repro.train.steps import init_train_state, make_train_step


def _hlo_flops(compiled) -> float:
    return compiled.cost_analysis().get("flops", 0)


def main() -> None:
    store = make_store(6, replicas=2)
    vol = GlobalVOL(store)
    spec = CorpusSpec(n_seqs=512, seq_len=256, vocab_size=100_000, seed=3)
    build_corpus(vol, spec, policy=PartitionPolicy(
        target_object_bytes=256 << 10, max_object_bytes=4 << 20))

    cfg = get_config("yi_9b", smoke=True)
    model = build_model(cfg, remat="none")
    state = init_train_state(model, jax.random.PRNGKey(0))
    base = make_train_step(model, OptConfig())
    B = 16

    plain_ld = ObjectDataLoader(vol, "corpus", global_batch=B, prefetch=0)
    packed_ld = ObjectDataLoader(vol, "corpus", global_batch=B,
                                 prefetch=0, packed=True)

    t0 = time.perf_counter()
    for s in range(8):
        pb = plain_ld.make_batch(s)
    plain_fetch = (time.perf_counter() - t0) / 8
    t0 = time.perf_counter()
    for s in range(8):
        kb = packed_ld.make_batch(s)
    packed_fetch = (time.perf_counter() - t0) / 8

    # streamed: windowed loader + device lookahead (the full pipeline —
    # per-OSD frames assemble batches early, next batch's words land on
    # device while the caller works on the current one)
    stream_ld = ObjectDataLoader(vol, "corpus", global_batch=B,
                                 prefetch=2, packed=True, window_steps=4)
    stream = device_stream(stream_ld, lookahead=1)
    next(stream)  # warm the first window
    t0 = time.perf_counter()
    for _ in range(8):
        next(stream)
    stream_fetch = (time.perf_counter() - t0) / 8
    stream_ld.close()

    plain_step = jax.jit(base)
    fused_step = jax.jit(make_fused_train_step(base))
    c_plain = plain_step.lower(
        state, {k: jnp.asarray(v) for k, v in pb.items()}).compile()
    c_fused = fused_step.lower(state, jnp.asarray(kb["tokens_packed"])) \
        .compile()

    a_plain = pb["tokens"].nbytes + pb["labels"].nbytes
    a_fused = kb["tokens_packed"].nbytes
    print("ingest_fused (B=16, S=256, vocab=100k -> 17-bit packing)")
    print(f"{'path':<8}{'batch_KB':>10}{'fetch_ms':>10}{'hlo_flops':>12}")
    print(f"{'plain':<8}{a_plain / 1024:>10.1f}{plain_fetch * 1e3:>10.1f}"
          f"{_hlo_flops(c_plain):>12.3e}")
    print(f"{'fused':<8}{a_fused / 1024:>10.1f}{packed_fetch * 1e3:>10.1f}"
          f"{_hlo_flops(c_fused):>12.3e}")
    print(f"{'stream':<8}{a_fused / 1024:>10.1f}"
          f"{stream_fetch * 1e3:>10.1f}{'(fused, windowed)':>12}")
    print(f"input-bytes reduction: {a_plain / a_fused:.2f}x "
          f"(theoretical {64 / 17:.2f}x for 17-bit tokens+derived labels)")
    # numerical equivalence of the two steps
    s1, m1 = plain_step(state, {k: jnp.asarray(v) for k, v in pb.items()})
    s2, m2 = fused_step(state, jnp.asarray(kb["tokens_packed"]))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    print("loss(plain) == loss(fused) -> OK")


if __name__ == "__main__":
    main()
