"""Work and bytes of the kernels and steps the benchmark times, computed
from shapes alone.  Kept here, apart from the program, so that a change
to the program cannot change how its work is counted."""

from __future__ import annotations

BITUNPACK_BLOCK_ROWS = 256  # rows per grid step the kernel is launched with


def bitunpack_launch_rows(n_values: int) -> int:
    """Rows of the (rows, 4, bits) launch that decodes ``n_values``
    bitpacked values: 32 values a group, 4 groups a 128-lane row, and
    the row count padded the way the host adapter pads it — one block
    when it fits, else blocks of equal height rounded up to a multiple
    of 8."""
    groups = -(-n_values // 32)
    rows = -(-groups // 4)
    n_blocks = max(1, -(-rows // BITUNPACK_BLOCK_ROWS))
    if n_blocks == 1:
        return rows
    bm = -(-rows // n_blocks)
    bm = -(-bm // 8) * 8
    return n_blocks * bm


def bitunpack_bytes(n_values: int, bits: int) -> int:
    """HBM bytes one decode launch moves: its words in (4 * bits int32
    words a row) and its values out (128 int32 a row)."""
    rows = bitunpack_launch_rows(n_values)
    return rows * 4 * bits * 4 + rows * 128 * 4


def lm_train_flops_per_token(model: dict, seq_len: int) -> float:
    """Operations a decoder LM's training step needs per token: 6 per
    matmul parameter (forward and backward), plus 12 * layers * seq *
    n_heads * head_dim for the attention scores and their use.  The
    embedding lookup is no matmul; recomputed work does not count."""
    d = model["hidden_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim", d // h)
    ff = model["intermediate_size"]
    layers = model["num_hidden_layers"]
    v = model["vocab_size"]
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff
    matmul_params = layers * per_layer + d * v   # + the output head
    return 6.0 * matmul_params + 12.0 * layers * seq_len * h * hd
