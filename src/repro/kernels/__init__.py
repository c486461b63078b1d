"""Pallas TPU kernels: the SplitZip codec and attention over compressed
KV pages.  Every entry point takes ``interpret=None``, which compiles the
kernel to Mosaic on a TPU and runs it in the Pallas interpreter elsewhere."""

import jax


def auto_interpret(interpret: bool | None) -> bool:
    """Resolve ``interpret=None`` by platform: compiled on a TPU only."""
    return jax.default_backend() != "tpu" if interpret is None else interpret
