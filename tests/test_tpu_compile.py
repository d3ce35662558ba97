"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each test lowers and compiles for one chip of a described
``v5e:2x2`` topology (the TPU compiler is installed even where no chip
is attached) and checks that the Mosaic kernel made it into the
program (``tpu_custom_call``).  This is what interpret-mode tests cannot
see: block shapes the TPU tiling refuses and ops Mosaic cannot lower.

The topology is described inside a module-scoped fixture, never at
import, so every pytest-xdist worker collects the same tests and only
the worker given this file loads the TPU library.  The persistent
compilation cache is off while these compile: an entry written for a
described chip cannot be read back without one.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import block_agg, filter_agg
from repro.kernels.bitunpack import _unpack_batch, pad_to_grid

# one 8 MiB float32 column: 2 Mi values
COLUMN_8MIB = (8 << 20) // 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _batch_spec(layout, sharding):
    size = sum(rows * 4 * bits for rows, bits in layout)
    return jax.ShapeDtypeStruct((size,), jnp.int32, sharding=sharding)


@pytest.mark.parametrize("rows", [5376, 4096, 257, 1000])
def test_bitunpack_compiles_at_scan_shapes(one_chip, rows):
    # 5376 and 4096 rows are the key-column launches of chip_smoke.py's
    # 2^25-row scan (688,128- and 524,288-row objects at the default
    # 8 MiB PartitionPolicy); 257 and 1000 need rebalanced multi-block
    # grids.  The host adapter (bitunpack_columns) pads rows with
    # pad_to_grid before the launch, so that padded shape is compiled.
    layout = ((pad_to_grid(rows)[1], 17),)
    text = _compiled_text(
        lambda w: _unpack_batch(w, layout=layout, interpret=False),
        _batch_spec(layout, one_chip))
    assert "tpu_custom_call" in text


LINEITEM_BITS = (23, 18, 14, 3, 6, 12, 12, 12)
Q6_BITS = (6, 12)  # l_quantity, l_shipdate


@pytest.mark.parametrize(
    "n,widths", [(60416, LINEITEM_BITS), (20031, LINEITEM_BITS),
                 (60416, Q6_BITS), (20031, Q6_BITS)],
    ids=["60416", "20031", "60416-q6", "20031-q6"])
def test_bitunpack_batch_compiles_at_lineitem_object(one_chip, n, widths):
    # the lineitem benchmark's full (60,416-row) and last (20,031-row)
    # objects: their 8 bitpacked columns (select, range) or Q6's 2, in
    # one program, one launch each
    rows = pad_to_grid(-(-(-(-n // 32)) // 4))[1]
    layout = tuple((rows, bits) for bits in widths)
    text = _compiled_text(
        lambda w: _unpack_batch(w, layout=layout, interpret=False),
        _batch_spec(layout, one_chip))
    assert text.count('custom_call_target="tpu_custom_call"') == len(layout)


def test_unpack_tokens_pallas_compiles_at_100m_batch(one_chip):
    from repro.data.fused_ingest import unpack_tokens
    # examples/train_e2e.py 100m preset: batch 8, seq 256, vocab 32,000
    # -> 15-bit packing, (8, 256 // 32, 15) words
    packed = jax.ShapeDtypeStruct((8, 8, 15), jnp.uint32, sharding=one_chip)
    text = _compiled_text(lambda p: unpack_tokens(p, use_pallas=True),
                          packed)
    assert "tpu_custom_call" in text


def test_filter_agg_compiles_at_8mib_column(one_chip):
    col = jax.ShapeDtypeStruct((COLUMN_8MIB,), jnp.float32,
                               sharding=one_chip)
    text = _compiled_text(
        lambda v, f: filter_agg.combine_partials(
            filter_agg.filter_agg(v, f, "<", 0.5, interpret=False)),
        col, col)
    assert "tpu_custom_call" in text


def test_block_agg_compiles_at_8mib_column(one_chip):
    col = jax.ShapeDtypeStruct((COLUMN_8MIB,), jnp.float32,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((COLUMN_8MIB,), jnp.int32,
                                sharding=one_chip)
    text = _compiled_text(
        lambda v, m: filter_agg.combine_partials(
            block_agg.block_agg(v, m, interpret=False)),
        col, mask)
    assert "tpu_custom_call" in text
