"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per the assignment: sweep shapes/dtypes and assert_allclose against the
ref.py oracle for each kernel.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.format import bitpack_encode
from repro.kernels import ops, ref


@pytest.mark.parametrize("bits", [1, 5, 8, 13, 16, 17, 20])
@pytest.mark.parametrize("shape", [(1, 128), (4, 512), (2, 1024)])
def test_bitunpack_sweep(bits, shape):
    rng = np.random.default_rng(bits)
    B, S = shape
    toks = rng.integers(0, 1 << bits, (B, S)).astype(np.int32)
    words = bitpack_encode(toks.ravel(), bits).reshape(B, S // 32, bits)
    out = ops.bitunpack_tokens(jnp.asarray(words), bits=bits)
    np.testing.assert_array_equal(np.asarray(out), toks)
    r = ref.bitunpack_ref(jnp.asarray(words.reshape(-1, 4, bits)), bits)
    np.testing.assert_array_equal(np.asarray(r).reshape(B, S), toks)


@pytest.mark.parametrize("cmp", ["<", "<=", ">", ">=", "==", "!="])
@pytest.mark.parametrize("n", [8192, 12345])
def test_filter_agg_sweep(cmp, n):
    rng = np.random.default_rng(hash(cmp) % 1000)
    v = rng.normal(size=n).astype(np.float32)
    f = rng.integers(0, 50, n).astype(np.float32)
    got = ops.filter_aggregate(jnp.asarray(v), jnp.asarray(f), cmp, 25)
    want = ref.filter_agg_ref(jnp.asarray(v), jnp.asarray(f), cmp, 25)
    for k in want:
        np.testing.assert_allclose(float(got[k]), float(want[k]),
                                   rtol=3e-5, atol=1e-3)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("n", [8192, 9000, 40000])
def test_block_agg_sweep(dtype, n):
    rng = np.random.default_rng(n)
    v = (rng.normal(size=n) * 10).astype(dtype)
    m = rng.random(n) < 0.5
    got = ops.masked_aggregate(jnp.asarray(v, jnp.float32),
                               jnp.asarray(m))
    want = ref.block_agg_ref(jnp.asarray(v, jnp.float32), jnp.asarray(m))
    for k in want:
        np.testing.assert_allclose(float(got[k]), float(want[k]),
                                   rtol=3e-5, atol=1e-3)


def test_filter_agg_empty_selection():
    v = jnp.ones((8192,), jnp.float32)
    f = jnp.zeros((8192,), jnp.float32)
    got = ops.filter_aggregate(v, f, ">", 1.0)
    assert float(got["count"]) == 0.0
    assert float(got["sum"]) == 0.0


def test_kernel_matches_host_codec_end_to_end():
    """Object bytes -> select_packed -> device bitunpack == raw tokens."""
    from repro.core import format as fmt
    from repro.core import objclass as oc
    rng = np.random.default_rng(7)
    toks = rng.integers(0, 100_000, (16, 128)).astype(np.int32)
    bits = fmt.bitpack_width(100_000 - 1)
    blob = fmt.encode_block({"tokens": toks},
                            codecs={"tokens": f"bitpack{bits}"})
    res = oc.select_packed(blob, rows=(3, 11), col="tokens")
    out = ops.bitunpack_tokens(jnp.asarray(res["packed"]),
                               bits=int(res["bits"]))
    np.testing.assert_array_equal(np.asarray(out), toks[3:11])


@pytest.mark.parametrize("rows", [1, 7, 8, 255, 256, 257, 1000, 4096, 6553])
def test_pad_to_grid_blocks_fit_the_tpu_tiling(rows):
    from repro.kernels.bitunpack import DEFAULT_BLOCK_R, pad_to_grid
    bm, padded = pad_to_grid(rows)
    n_blocks = padded // bm
    assert padded % bm == 0 and padded >= rows and bm <= DEFAULT_BLOCK_R
    if n_blocks > 1:
        assert bm % 8 == 0                   # TPU sublane tile
        assert padded - rows < 8 * n_blocks  # bounded padding
    else:
        assert padded == rows                # one block spans the array


@pytest.mark.parametrize("bits", [1, 17, 24, 32])
@pytest.mark.parametrize("rows", [257, 1000])
def test_bitunpack_words_multi_block_bit_exact(bits, rows):
    """Several grid steps, rebalanced block heights, and the 32-bit
    codec whose groups fill their 32-lane segments with no zero planes."""
    from repro.core.format import bitpack_decode
    from repro.kernels.bitunpack import bitunpack_columns
    rng = np.random.default_rng(rows + bits)
    n = rows * 128 - 5
    v = rng.integers(0, 1 << bits, n, dtype=np.uint64).astype(np.uint32)
    words = bitpack_encode(v, bits)
    (got,) = bitunpack_columns([(words, bits, n)], interpret=True)
    np.testing.assert_array_equal(got, bitpack_decode(words, bits, n))
    np.testing.assert_array_equal(got, v)


# (bits, n) of each column of one batch; 60,416 and 20,031 rows are the
# lineitem benchmark's full and last objects, at its 8 bitpacked widths
_BATCHES = {
    "mixed_widths": [(b, 4000 + 37 * b)
                     for b in (1, 3, 6, 12, 14, 18, 23, 24, 32)],
    "one_column": [(13, 1000)],
    "zero_length_alone": [(7, 0)],
    "zero_length_in_batch": [(5, 300), (9, 0), (17, 33)],
    "lineitem_full_object": [(b, 60416)
                             for b in (23, 18, 14, 3, 6, 12, 12, 12)],
    "lineitem_last_object": [(b, 20031)
                             for b in (23, 18, 14, 3, 6, 12, 12, 12)],
}


@pytest.mark.parametrize("case", sorted(_BATCHES))
def test_bitunpack_columns_batch_bit_exact(case):
    """A batch decodes bit-exact with the numpy codec, column by column,
    in one round trip that launches every non-empty column once."""
    from repro.core.format import bitpack_decode
    from repro.kernels.bitunpack import bitunpack_columns, decode_stats
    rng = np.random.default_rng(len(case))
    spec = _BATCHES[case]
    cols = []
    for bits, n in spec:
        v = rng.integers(0, 1 << bits, n, dtype=np.uint64).astype(np.uint32)
        cols.append((bitpack_encode(v, bits), bits, n))
    before = decode_stats()
    got = bitunpack_columns(cols, interpret=True)
    after = decode_stats()
    assert len(got) == len(cols)
    for g, (words, bits, n) in zip(got, cols):
        assert g.dtype == np.uint32 and g.shape == (n,)
        np.testing.assert_array_equal(g, bitpack_decode(words, bits, n))
    launched = sum(1 for _, n in spec if n)
    assert after["calls"] - before["calls"] == launched
    assert after["trips"] - before["trips"] == int(launched > 0)


def test_bitunpack_columns_counts_one_layout_per_program():
    """Each distinct (rows, bits) layout of a batch is one program, and
    ``layouts`` counts them: a repeated layout adds none, another order
    or another width of the same columns adds one."""
    from repro.kernels.bitunpack import bitunpack_columns, decode_stats
    rng = np.random.default_rng(3)

    def col(bits, n=700):
        v = rng.integers(0, 1 << bits, n, dtype=np.uint64).astype(np.uint32)
        return bitpack_encode(v, bits), bits, n

    a, b = col(5), col(11)
    runs = [[a, b], [a, b], [b, a], [a, col(12)]]
    seen = []
    for cols in runs:
        bitunpack_columns(cols, interpret=True)
        seen.append(decode_stats()["layouts"])
    assert [s - seen[0] for s in seen] == [0, 0, 1, 2]
