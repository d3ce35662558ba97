"""Random weights of a decoder LM, from a seed, leaf by name.

Every leaf is named (``embed.tok``, ``blocks.<layer>.attn.wq``,
``final_norm.scale``, ...) and drawn from its own key, the seed's key
folded with the CRC-32 of its name, so the program's stacked tree and
the reference's per-layer dict hold the same numbers however each
orders them.  Matrices are normal with standard deviation
fan_in ** -0.5; norm scales are ones.
"""

from __future__ import annotations

import zlib

import numpy as np


def base_key(seed: int):
    import jax
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def fan_in(name: str, shape: tuple[int, ...]) -> int:
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("wq", "wk", "wv", "w1", "w3", "head"):
        return shape[0]            # (d_model, ...) -> ...
    if leaf == "tok":
        return shape[1]            # (vocab, d_model)
    return int(np.prod(shape[:-1]))  # wo (heads, head_dim, d), w2 (ff, d)


def leaf(key, name: str, shape: tuple[int, ...], dtype):
    """One leaf in ``dtype``: drawn in float32, then cast."""
    import jax
    import jax.numpy as jnp
    if name.endswith(".scale"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    w = jax.random.normal(k, shape, jnp.float32) * fan_in(name, shape) ** -0.5
    return w.astype(dtype)
