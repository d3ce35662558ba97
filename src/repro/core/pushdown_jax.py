"""Device data-plane pushdown — the paper's offload idea, TPU-native.

On a TPU pod there is no storage-server CPU to push object-class code
into; the analogue of "the server that holds the object" is *the device
that holds the shard*.  "Offload to storage" therefore becomes "compute
where the shard lives, move only results": these helpers run objclass-
style operators inside ``shard_map`` regions over the data axes, so the
only bytes entering collectives are the (tiny) partials — the paper's
O(data) -> O(result) traffic reduction, visible directly in the
collective-bytes roofline term of the compiled HLO.

``unpack_bitpacked`` is the storage-side *decompress* offload: objects
hold planar-bitpacked tokens (core.format codec, kernels/bitunpack Pallas
twin); the unpack runs shard-locally inside the compiled train step, so
the host->device and HBM input path carries b/32 of the raw bytes.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd

_PRED = {
    "<": jax.lax.lt, "<=": jax.lax.le, ">": jax.lax.gt,
    ">=": jax.lax.ge, "==": jax.lax.eq, "!=": jax.lax.ne,
}


# --------------------------------------------------------------------------
# codec offload: planar bitunpack (pure-jnp; kernels/bitunpack has the Pallas
# version — this one is the GSPMD-partitionable reference the steps use)
# --------------------------------------------------------------------------


def unpack_bitpacked(words: jax.Array, bits: int) -> jax.Array:
    """(..., G, bits) uint32 planar words -> (..., G*32) int32 values.

    Elementwise + tiny reduction: GSPMD partitions it over any batch
    sharding with zero collectives, so the decompress truly runs where
    the shard lives.
    """
    if words.shape[-1] != bits:
        raise ValueError(f"last dim {words.shape[-1]} != bits {bits}")
    lane = jnp.arange(32, dtype=jnp.uint32)
    # (..., G, bits, 32): bit k of each of the 32 lane values
    sliced = (words[..., None] >> lane) & jnp.uint32(1)
    weights = (jnp.uint32(1) << jnp.arange(bits, dtype=jnp.uint32))
    vals = jnp.sum(sliced * weights[:, None], axis=-2, dtype=jnp.uint32)
    return vals.reshape(*words.shape[:-2], -1).astype(jnp.int32)


def packed_shape(n_values: int, bits: int) -> tuple[int, int]:
    """Shape of the packed representation of n_values values."""
    return (-(-n_values // 32), bits)


# --------------------------------------------------------------------------
# shard-local filter/aggregate (objclass ops as shard_map regions)
# --------------------------------------------------------------------------


def _partial_filter_agg(values, filter_col, cmp: str, threshold,
                        dp_axes) -> dict:
    """Per-shard objclass pipeline: filter(col cmp thr) -> agg partials.
    Output is O(1) — only these scalars cross the ICI."""
    mask = _PRED[cmp](filter_col, threshold)
    vf = values.astype(jnp.float32)
    big = jnp.float32(3.4e38)
    sel = jnp.where(mask, vf, 0.0)
    partial = {
        "sum": jnp.sum(sel),
        "count": jnp.sum(mask.astype(jnp.float32)),
        "min": jnp.min(jnp.where(mask, vf, big)),
        "max": jnp.max(jnp.where(mask, vf, -big)),
    }
    if dp_axes:
        partial = {
            "sum": jax.lax.psum(partial["sum"], dp_axes),
            "count": jax.lax.psum(partial["count"], dp_axes),
            "min": jax.lax.pmin(partial["min"], dp_axes),
            "max": jax.lax.pmax(partial["max"], dp_axes),
        }
    return partial


def pushdown_filter_aggregate(values: jax.Array, filter_col: jax.Array,
                              cmp: str, threshold) -> dict:
    """Distributed filter+aggregate with O(result) collective bytes.

    ``values``/``filter_col``: (N,) arrays sharded over the data axes.
    Without an active mesh this runs unsharded (smoke tests).
    """
    rules = shd.active_rules()
    if rules is None:
        return _partial_filter_agg(values, filter_col, cmp, threshold, None)
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else rules.dp_axes[0]
    fn = functools.partial(_partial_filter_agg, cmp=cmp,
                           threshold=threshold, dp_axes=rules.dp_axes)
    return jax.shard_map(
        lambda v, f: fn(v, f),
        mesh=rules.mesh,
        in_specs=(P(dp), P(dp)),
        out_specs={k: P() for k in ("sum", "count", "min", "max")},
        check_vma=False,
    )(values, filter_col)


# --------------------------------------------------------------------------
# generic compute-at-shard combinator
# --------------------------------------------------------------------------


def shard_local(fn: Callable, *, out_specs, in_axes: str = "dp"):
    """Wrap ``fn(shard_inputs...) -> partials`` to run where the data
    shards live.  ``fn`` receives per-shard blocks and must emit already-
    combined outputs (use ``jax.lax.psum`` etc. with axis name(s) given by
    ``repro.distributed.sharding.active_rules().dp_axes``).

    The deliberate contract mirrors the paper's objclass API: the local
    function sees only its object's bytes; anything global must go
    through an explicit (accounted) collective.
    """
    rules = shd.active_rules()
    if rules is None:
        return fn
    dp = rules.dp_axes if len(rules.dp_axes) > 1 else rules.dp_axes[0]
    spec = P(dp) if in_axes == "dp" else P(*in_axes)
    return jax.shard_map(fn, mesh=rules.mesh,
                         in_specs=spec, out_specs=out_specs, check_vma=False)
