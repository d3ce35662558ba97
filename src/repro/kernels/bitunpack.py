"""Pallas TPU kernel: planar bitpack decode (storage codec offload).

Input layout (core.format planar codec): each group of 32 values is b
uint32 words; word k holds bit k of all 32 values.  We process 4 groups
per output row so the output tile is 128-lane aligned for the VPU:

  words  (R, 4, b)  uint32   ->   values (R, 128) int32

The kernel sees each row as its 4*b words side by side (a free reshape
of the same bytes), widens every group to a 32-lane segment (b words,
then zero planes), and decodes with the same 32x32 bit transpose as the
numpy codec (``format._bit_transpose32``): five masked shift-swap
stages, with the partner word of each stage one lane roll away.  Only
32-bit integer VPU ops and lane rolls, no reductions and no MXU work.

Tiling: a (BLOCK_R, 4*b) word tile is BLOCK_R * 512 bytes of VMEM once
padded to 128 lanes, and the (BLOCK_R, 128) output tile the same; with
BLOCK_R=256 that is 128 KiB each, well inside the VMEM budget with
double buffering.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.obs import span

DEFAULT_BLOCK_R = 256

# (swap distance, mask) per stage of the 32x32 bit transpose — the same
# stages as ``format._BUTTERFLY``; every mask fits a positive int32
_STAGES = ((16, 0x0000FFFF), (8, 0x00FF00FF), (4, 0x0F0F0F0F),
           (2, 0x33333333), (1, 0x55555555))


def pad_to_grid(rows: int, block_r: int = DEFAULT_BLOCK_R
                ) -> tuple[int, int]:
    """Choose (block_r, padded_rows) for an R-row launch: the grid-step
    count comes from ``block_r``, then the block height is rebalanced to
    ceil(rows / n_blocks) rounded up to a multiple of 8 (the TPU's
    sublane tile; a single block spans the whole array and needs no
    rounding), so padding stays under 8 * n_blocks rows — padding
    straight up to a ``block_r`` multiple would nearly double the
    kernel work at rows = block_r + 1."""
    n_blocks = max(1, -(-rows // block_r))
    if n_blocks == 1:
        return rows, rows
    bm = -(-rows // n_blocks)
    bm = -(-bm // 8) * 8
    return bm, n_blocks * bm


def _bitunpack_kernel(w_ref, o_ref, *, bits: int):
    w = w_ref[...]                                  # (bm, 4*bits) int32
    bm = w.shape[0]
    parts = []
    for g in range(4):                              # group g -> lanes 32g..
        parts.append(w[:, g * bits:(g + 1) * bits])
        if bits < 32:
            parts.append(jnp.zeros((bm, 32 - bits), jnp.int32))
    x = jnp.concatenate(parts, axis=1)              # (bm, 128): lane 32g+k
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, 128), 1)
    for j, m in _STAGES:
        up = pltpu.roll(x, 128 - j, 1)              # lane l <- x[l + j]
        down = pltpu.roll(x, j, 1)                  # lane l <- x[l - j]
        t_lo = (jax.lax.shift_right_logical(x, j) ^ up) & m
        t_hi = (jax.lax.shift_right_logical(down, j) ^ x) & m
        x = jnp.where((lane & j) == 0, x ^ (t_lo << j), x ^ t_hi)
    o_ref[...] = x


def bitunpack(words: jax.Array, *, bits: int,
              block_r: int = DEFAULT_BLOCK_R,
              interpret: bool = False) -> jax.Array:
    """(R, 4, bits) uint32 -> (R, 128) int32 via pallas_call; any R
    (rows are padded up to the grid ``pad_to_grid`` picks and sliced
    back off)."""
    R = words.shape[0]
    if words.shape[1:] != (4, bits) or not 1 <= bits <= 32:
        raise ValueError(f"want (R, 4, {bits}), got {words.shape}")
    w = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(R, 4 * bits)
    return _bitunpack_rows(w, bits=bits, block_r=block_r,
                           interpret=interpret)


def _bitunpack_rows(w: jax.Array, *, bits: int,
                    block_r: int = DEFAULT_BLOCK_R,
                    interpret: bool = False) -> jax.Array:
    """The launch of :func:`bitunpack` on its (R, 4 * bits) int32 row
    form, each row's 4 groups side by side -> (R, 128) int32."""
    R = w.shape[0]
    bm, padded = pad_to_grid(R, block_r)
    if padded != R:
        w = jnp.pad(w, ((0, padded - R), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_bitunpack_kernel, bits=bits),
        grid=(padded // bm,),
        in_specs=[pl.BlockSpec((bm, 4 * bits), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, 128), jnp.int32),
        interpret=interpret,
        name="bitunpack",
    )(w)
    return out[:R] if padded != R else out


def _unpack_batch(buf: jax.Array, *, layout: tuple[tuple[int, int], ...],
                  interpret: bool) -> jax.Array:
    """Columns' words laid back to back in one flat int32 ``buf`` ->
    their values in one (rows, 128) int32 array, column after column:
    one ``bitunpack`` launch per ``(rows, bits)`` entry of ``layout``,
    each at the rows the host already padded to its grid."""
    outs, off = [], 0
    for rows, bits in layout:
        size = rows * 4 * bits
        # the barrier keeps XLA from turning slice-then-reshape into a
        # relayout of the whole buffer to this column's row width
        w = jax.lax.optimization_barrier(buf[off:off + size])
        w = w.reshape(rows, 4 * bits)
        outs.append(_bitunpack_rows(w, bits=bits, interpret=interpret))
        off += size
    return jnp.concatenate(outs)


_unpack_batch_jit = jax.jit(_unpack_batch,
                            static_argnames=("layout", "interpret"))

# decode counters of the host adapter: ``bitunpack`` launches, the
# round trips that carry them, the launches run in interpret mode, the
# distinct (rows, bits) launches, and the distinct batch layouts — on a
# TPU each new layout is a compile of its program
_stats_lock = threading.Lock()
_stats = {"calls": 0, "trips": 0, "interpret_calls": 0}
_launch_shapes: set[tuple[int, int]] = set()
_layouts: set[tuple[tuple[int, int], ...]] = set()


def decode_stats() -> dict:
    """Snapshot of the host adapter's counters (see
    ``bitunpack_columns``): ``calls / trips`` is the batch width."""
    with _stats_lock:
        return dict(_stats, shapes=len(_launch_shapes),
                    layouts=len(_layouts))


def reset_decode_stats() -> None:
    with _stats_lock:
        _stats.update(calls=0, trips=0, interpret_calls=0)
        _launch_shapes.clear()
        _layouts.clear()


def bitunpack_columns(cols: list[tuple[np.ndarray, int, int]], *,
                      interpret: bool | None = None) -> list[np.ndarray]:
    """Decode a batch of planar bitpacked columns in one device round
    trip: each ``(words, bits, n)`` — (G, bits) uint32 words of n
    values — comes back as an (n,) uint32 array.

    Host-side adapter for the storage scan path (``format.decode_block``
    hands it every bitpacked column of a block it decodes): pads each
    column's group count up to the (rows, 4, bits) grid ``pad_to_grid``
    gives it, lays the padded words back to back in one host buffer,
    moves that with one transfer, runs one program with one ``bitunpack``
    launch per column (compiled on a TPU, interpreted elsewhere — see
    ``kernels.interpret_mode``), fetches every value with one copy back
    and cuts each column's padding off.  Bit-exact with
    ``format.bitpack_decode`` — the zero pad groups decode to zeros and
    are dropped.  A column with no values needs no launch; a batch of
    them makes no trip.  The program is compiled once per ``layout``,
    the tuple of its columns' (rows, bits): a scan that meets a new
    projection or a new mix of per-object widths compiles once more
    (``decode_stats()["layouts"]`` counts them).  The whole adapter
    (pad, transfer, launch, fetch) runs in one ``codec.bitunpack``
    span.
    """
    with span("codec.bitunpack"):
        out = [np.zeros((0,), np.uint32) for _ in cols]
        live, layout = [], []  # columns with values, their (rows, bits)
        for i, (words, bits, _) in enumerate(cols):
            groups = np.size(words) // bits
            if groups:
                live.append(i)
                layout.append((pad_to_grid(-(-groups // 4))[1], bits))
        if not live:
            return out
        layout = tuple(layout)
        buf = np.zeros(sum(rows * 4 * bits for rows, bits in layout),
                       np.uint32)
        off = 0
        for i, (rows, bits) in zip(live, layout):
            w = np.asarray(cols[i][0], dtype=np.uint32).ravel()
            buf[off:off + w.size] = w  # the rest of its rows: zero pad
            off += rows * 4 * bits
        if interpret is None:
            interpret = interpret_mode()
        with _stats_lock:
            _stats["calls"] += len(live)
            _stats["trips"] += 1
            _stats["interpret_calls"] += len(live) * int(interpret)
            _launch_shapes.update(layout)
            _layouts.add(layout)
        vals = np.asarray(_unpack_batch_jit(
            jnp.asarray(buf.view(np.int32)), layout=layout,
            interpret=interpret)).view(np.uint32).ravel()
        off = 0
        for i, (rows, _) in zip(live, layout):
            out[i] = vals[off:off + cols[i][2]]
            off += rows * 128
        return out
