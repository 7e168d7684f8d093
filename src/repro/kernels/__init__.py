"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel module pairs with a pure-jnp oracle in ``ref.py``; ``ops.py``
exposes the jit'd wrappers the model layer dispatches to via
``Runtime.use_pallas``.  On the CPU backend kernels run in interpret
mode; on a TPU the same ``pallas_call``s compile via Mosaic
(``ops.interpret_mode``).
"""
