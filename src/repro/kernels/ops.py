"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile to Mosaic; elsewhere they run with
``interpret=True``.  ``kernels.interpret_mode`` keys off the default
backend so call sites never branch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bitunpack as _bu
from repro.kernels import block_agg as _ba
from repro.kernels import filter_agg as _fa
from repro.kernels import interpret_mode


@functools.partial(jax.jit, static_argnames=("bits", "block_r"))
def bitunpack_tokens(words: jax.Array, *, bits: int,
                     block_r: int = _bu.DEFAULT_BLOCK_R) -> jax.Array:
    """(B, G, bits) packed batch -> (B, G*32) int32 tokens.

    Reshapes to the kernel's (R, 4, bits) row form; requires G % 4 == 0
    (i.e. seq_len % 128 == 0 — true for every assigned shape).
    """
    B, G, b = words.shape
    if b != bits or G % 4:
        raise ValueError(f"bad packed shape {words.shape}")
    rows = words.reshape(B * G // 4, 4, bits)
    out = _bu.bitunpack(rows, bits=bits, block_r=block_r,
                        interpret=interpret_mode())
    return out.reshape(B, G * 32)


@functools.partial(jax.jit,
                   static_argnames=("cmp", "threshold", "block_rows"))
def filter_aggregate(values: jax.Array, filter_col: jax.Array, cmp: str,
                     threshold, *,
                     block_rows: int = _fa.DEFAULT_BLOCK_ROWS) -> dict:
    """Fused filter+agg; pads N up to a tile boundary with mask-failing
    rows so any N works."""
    N = values.shape[0]
    tile = block_rows * 128
    pad = (-N) % tile
    if pad:
        values = jnp.pad(values, (0, pad))
        # pad filter with a value that fails the predicate: NaN compares
        # False under < <= > >= ==; for != use the threshold itself.
        pad_val = float(threshold) if cmp == "!=" else float("nan")
        filter_col = jnp.pad(filter_col.astype(jnp.float32), (0, pad),
                             constant_values=pad_val)
    partials = _fa.filter_agg(values, filter_col, cmp, float(threshold),
                              block_rows=block_rows,
                              interpret=interpret_mode())
    return _fa.combine_partials(partials)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def masked_aggregate(values: jax.Array, mask: jax.Array, *,
                     block_rows: int = _ba.DEFAULT_BLOCK_ROWS) -> dict:
    N = values.shape[0]
    tile = block_rows * 128
    pad = (-N) % tile
    if pad:
        values = jnp.pad(values, (0, pad))
        mask = jnp.pad(mask.astype(jnp.int32), (0, pad))
    partials = _ba.block_agg(values, mask, block_rows=block_rows,
                             interpret=interpret_mode())
    return _fa.combine_partials(partials)
