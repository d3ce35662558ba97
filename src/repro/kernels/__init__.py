"""Pallas kernels for the storage codec and the OSD filter/aggregate
ops (``bitunpack``, ``filter_agg``, ``block_agg``; jitted wrappers in
``ops``, pure-jnp oracles in ``ref``)."""


def interpret_mode() -> bool:
    """Whether a kernel launch runs in Pallas interpret mode: never on a
    TPU backend, where every kernel compiles through Mosaic; always
    elsewhere, where the same call sites stay testable.  The one place
    the rule is decided — callers that want interpret mode regardless
    pass ``interpret=True`` themselves."""
    import jax
    return jax.default_backend() != "tpu"
