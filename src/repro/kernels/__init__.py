"""Pallas TPU kernels for the serving engine's compute hot-spots.

Each kernel directory contains:
  <name>.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public wrapper (compiled on TPU, interpret=True on CPU)
  ref.py     pure-jnp oracle used by the allclose test sweeps

TPU adaptation notes (DESIGN.md §3): block shapes are MXU-aligned
(multiples of 128 on matmul dims where dtypes allow), online-softmax
carries live in VMEM scratch across the sequential grid dimension, and
GQA head-mapping happens in the index_map (no gather).
"""
import jax


def interpret_mode() -> bool:
    """Pallas `interpret` flag for the default backend: compiled on TPU,
    interpreted on CPU (the test suite).  Any other backend raises, so
    no run falls back to the interpreter without saying so."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")
