"""Fused in-step ingest: storage-side compression decoded on-device.

The paper's `compress` offload, adapted to the TPU input path: objects
store tokens planar-bitpacked; the loader ships the *packed words* to the
device, and the unpack (+ label derivation, which the storage layer knows
is a row shift — dataset semantics made available to the system, paper
goal 1) happens inside the compiled train step, shard-locally.

Input-path bytes per token: 8 (tokens+labels int32) -> bits/8 (~2.1 for a
17-bit vocab) — a 3.8x reduction in host->device and HBM traffic for the
batch, with zero collectives added (elementwise unpack).
"""

from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.format import bitpack_width
from repro.core.pushdown_jax import unpack_bitpacked


def pack_batch(tokens: np.ndarray, bits: int) -> np.ndarray:
    """(B, S) int32 -> (B, S//32, bits) uint32 planar words (host side —
    i.e. what the OSD already stores; see objclass.select_packed)."""
    from repro.core.format import bitpack_encode
    B, S = tokens.shape
    if S % 32:
        raise ValueError("S must be a multiple of 32")
    return bitpack_encode(tokens.ravel(), bits).reshape(B, S // 32, bits)


def unpack_tokens(packed: jax.Array, *, use_pallas: bool = False,
                  interpret: bool = False) -> jax.Array:
    """(B, G, bits) uint32 -> (B, G*32) int32, in-graph.

    ``use_pallas`` routes the unpack through the hand-tiled VPU kernel
    (``kernels/bitunpack``; raises unless G % 4 == 0, the 128-lane row
    requirement) instead of the GSPMD-partitionable jnp reference —
    same planar layout, bit-identical values, but with explicit VMEM
    tiling for the TPU input path.  ``interpret`` runs that kernel in
    interpret mode (CPU tests).
    """
    B, G, bits = packed.shape
    if use_pallas:
        if G % 4:
            raise ValueError(f"use_pallas needs G % 4 == 0 "
                             f"(128-lane rows), got G={G}")
        from repro.kernels.bitunpack import bitunpack
        vals = bitunpack(packed.reshape(B * (G // 4), 4, bits), bits=bits,
                         interpret=interpret)
        return vals.reshape(B, G * 32)
    return unpack_bitpacked(packed, bits)


def derive_labels(tokens: jax.Array) -> jax.Array:
    """labels[t] = tokens[t+1]; last position masked.  The shift is the
    dataset's logical schema, applied where the shard lives."""
    labels = jnp.roll(tokens, -1, axis=1)
    return labels.at[:, -1].set(-1)


def fused_batch(packed: jax.Array) -> dict[str, jax.Array]:
    tokens = unpack_tokens(packed)
    return {"tokens": tokens, "labels": derive_labels(tokens)}


def device_stream(loader, *, lookahead: int = 1):
    """Iterate a *packed* loader as device-resident packed words with
    transfer lookahead — the device tail of the streaming input path.

    The loader (ideally ``prefetch > 0`` and ``window_steps > 1``)
    assembles host batches while slow OSDs are still serving later
    steps; this generator keeps ``lookahead`` batches' packed words
    already ``jax.device_put`` while the caller computes on the current
    one, so OSD frames -> host window -> device words -> in-graph
    unpack (``make_fused_train_step``) form one pipeline with no serial
    hop.  Yields the device array a fused step consumes directly.
    """
    q: deque = deque()
    it = iter(loader)

    def pull() -> None:
        try:
            q.append(jax.device_put(next(it)["tokens_packed"]))
        except StopIteration:
            pass

    for _ in range(max(lookahead, 0) + 1):
        pull()
    while q:
        words = q.popleft()
        pull()
        yield words


def make_fused_train_step(base_train_step):
    """Wrap a (state, batch)->(state, metrics) step to take packed words.

    The unpack lands inside the same XLA program, so cost_analysis of the
    fused step shows the input-bytes reduction directly.
    """

    def fused_step(state, packed):
        return base_train_step(state, fused_batch(packed))

    return fused_step


def packed_input_spec(global_batch: int, seq_len: int, vocab: int):
    """ShapeDtypeStruct for the packed batch (dry-run input stand-in)."""
    bits = bitpack_width(vocab - 1)
    return jax.ShapeDtypeStruct((global_batch, seq_len // 32, bits),
                                jnp.uint32)
