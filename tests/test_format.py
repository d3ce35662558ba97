"""Block format / codec properties."""

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import format as fmt
from repro.core import objclass as oc


@given(st.integers(1, 24), st.integers(0, 2000))
@settings(max_examples=30, deadline=None)
def test_bitpack_roundtrip(bits, n):
    rng = np.random.default_rng(bits * 1000 + n)
    v = rng.integers(0, 1 << bits, n).astype(np.uint32)
    words = fmt.bitpack_encode(v, bits)
    assert words.shape == (-(-n // 32) if n else 0, bits)
    out = fmt.bitpack_decode(words, bits, n)
    assert np.array_equal(out, v)


def test_bitpack_rejects_overflow():
    with pytest.raises(ValueError):
        fmt.bitpack_encode(np.array([8], np.uint32), 3)


@given(st.sampled_from(["none", "zlib", "bitpack12"]),
       st.integers(1, 300))
@settings(max_examples=20, deadline=None)
def test_block_roundtrip_col(codec, n):
    rng = np.random.default_rng(n)
    table = {
        "a": rng.integers(0, 4000, n).astype(np.int32),
        "b": rng.normal(size=(n, 3)).astype(np.float32),
    }
    codecs = {"a": codec} if codec != "none" else {}
    blob = fmt.encode_block(table, codecs=codecs)
    out = fmt.decode_block(blob)
    assert np.array_equal(out["a"], table["a"])
    assert np.allclose(out["b"], table["b"])


def test_block_projection_reads_subset():
    table = {"x": np.arange(10, dtype=np.int64),
             "y": np.ones((10, 2), np.float32)}
    blob = fmt.encode_block(table)
    out = fmt.decode_block(blob, columns=["y"])
    assert set(out) == {"y"}
    with pytest.raises(KeyError):
        fmt.decode_block(blob, columns=["nope"])


def test_layout_transform_roundtrip():
    rng = np.random.default_rng(0)
    table = {"x": rng.integers(0, 100, 50).astype(np.int32),
             "y": rng.normal(size=50)}
    col = fmt.encode_block(table, layout="col")
    row = fmt.transform_layout(col, "row")
    assert fmt.block_header(row)["layout"] == "row"
    back = fmt.transform_layout(row, "col")
    out = fmt.decode_block(back)
    assert np.array_equal(out["x"], table["x"])
    assert np.allclose(out["y"], table["y"])


def test_zone_map_in_header():
    blob = fmt.encode_block({"v": np.array([3.0, -1.0, 7.0])})
    zm = fmt.block_header(blob)["zone_map"]
    assert zm["v"] == [-1.0, 7.0]


def test_select_packed_zero_decode_equals_decoded_select():
    rng = np.random.default_rng(1)
    S, n = 64, 20
    toks = rng.integers(0, 5000, (n, S)).astype(np.int32)
    blob = fmt.encode_block({"tokens": toks},
                            codecs={"tokens": "bitpack13"})
    res = oc.select_packed(blob, rows=(5, 12), col="tokens")
    assert res["packed"].shape == (7, S // 32, 13)
    dec = fmt.bitpack_decode(res["packed"].reshape(-1, 13), 13,
                             7 * S).reshape(7, S)
    assert np.array_equal(dec.astype(np.int32), toks[5:12])


def _stamp_table(n=300):
    rng = np.random.default_rng(n)
    return {"x": rng.normal(size=n),
            "y": rng.integers(0, 1000, n).astype(np.int32),
            "z": rng.integers(-5, 5, n).astype(np.int64)}


def test_stamp_digests_tile_a_col_block_header_then_each_column():
    blob = fmt.encode_block(_stamp_table(), codecs={"y": "bitpack10",
                                                    "z": "zlib"})
    stamps = fmt.stamp_digests(blob)
    assert stamps["digest"] == fmt.content_digest(blob)
    header = fmt.block_header(blob)
    hlen = int.from_bytes(blob[4:8], "little")
    bounds = [0, 8 + hlen]
    for n in header["lens"]:
        bounds.append(bounds[-1] + n)
    assert bounds[-1] == len(blob)  # header plus the lens tile the blob
    assert stamps["extent_digests"] == [
        fmt.content_digest(blob[s:e]) for s, e in zip(bounds, bounds[1:])]
    assert len(stamps["extent_digests"]) == len(header["columns"]) + 1


@pytest.mark.parametrize("n", [0, 1, 300, 70_000])
def test_stamp_digests_folds_the_whole_digest_out_of_its_extents(n):
    blob = fmt.encode_block(_stamp_table(n), codecs={"y": "bitpack10",
                                                     "z": "zlib"})
    stamps = fmt.stamp_digests(blob)
    assert stamps["digest"] == fmt.content_digest(blob)
    assert len(stamps["extent_digests"]) == 4


@pytest.mark.parametrize("blob", [
    fmt.encode_block(_stamp_table(), layout="row"),
    b"not a block at all",
    b"SKYB" + (5).to_bytes(4, "little") + b"{not json",
    b"",
], ids=["row_layout", "bytes", "magic_only", "empty"])
def test_stamp_digests_gives_one_digest_to_anything_but_a_col_block(blob):
    assert fmt.stamp_digests(blob) == {"digest": fmt.content_digest(blob)}


def test_check_extents_reads_the_header_then_only_the_named_columns():
    blob = fmt.encode_block(_stamp_table(), codecs={"y": "bitpack10"})
    stamps = fmt.stamp_digests(blob)["extent_digests"]
    header = fmt.block_header(blob)
    hlen = int.from_bytes(blob[4:8], "little")
    lens = dict(zip([c["name"] for c in header["columns"]],
                    header["lens"]))
    assert fmt.check_extents(blob, stamps, ["y"]) == (
        True, 8 + hlen + lens["y"])
    assert fmt.check_extents(blob, stamps, []) == (True, 8 + hlen)
    # rot in a column the read names fails it; rot elsewhere does not
    x_at = 8 + hlen + lens["x"] // 2  # x is the first column
    rotten = bytearray(blob)
    rotten[x_at] ^= 1
    assert not fmt.check_extents(bytes(rotten), stamps, ["x"])[0]
    assert fmt.check_extents(bytes(rotten), stamps, ["y", "z"])[0]
    # rot in the header fails every read, whatever it names
    rotten = bytearray(blob)
    rotten[9] ^= 1
    assert not fmt.check_extents(bytes(rotten), stamps, [])[0]
    # stamps that do not match the header's column count are not ok
    assert not fmt.check_extents(blob, stamps[:-1], ["y"])[0]
