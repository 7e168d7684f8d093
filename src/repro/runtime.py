"""Runtime context threading mesh/axis/kernel decisions through model code.

Model functions are pure; the ``Runtime`` tells them how to behave in a
distributed setting (which mesh axes exist, whether to use shard_map expert
parallelism, whether to use Pallas kernels) without baking any of it into the
math.  ``Runtime()`` (all defaults) is the single-device configuration
on the jnp reference path; the paged serving engine selects the Pallas
kernels where they compile (``on_tpu``).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class Runtime:
    mesh: Optional[Mesh] = None
    # logical axis groups (tuples of mesh axis names; empty -> replicated)
    batch_axes: Tuple[str, ...] = ()      # batch dim of activations
    model_axes: Tuple[str, ...] = ()      # heads / d_ff / experts / vocab
    token_axes: Tuple[str, ...] = ()      # flattened-token dim for MoE dispatch
    seq_axes: Tuple[str, ...] = ()        # sequence dim (long-context decode)
    # Pallas kernels instead of the jnp references.  They compile on a
    # TPU and run interpreted on the CPU (kernels.ops.interpret_mode).
    use_pallas: bool = False
    remat: bool = False                   # activation checkpointing in train
    # Megatron-style sequence parallelism for the TRAIN layer-scan carry:
    # saved per-layer activations are sharded over 'model' on the sequence
    # dim (16x less HBM for checkpointed boundaries).  §Perf iteration 1.
    seq_parallel: bool = False
    # Decode-path MoE: compute on f-sharded resident expert weights
    # (token all-gather + partial-output psum over the data axes) instead
    # of gathering GBs of expert weights per layer.  §Perf kimi-decode.
    moe_fsharded: bool = False

    @property
    def ep_axis(self) -> Optional[str]:
        """Mesh axis used for expert-parallel all-to-all (last model axis)."""
        return self.model_axes[-1] if self.model_axes else None

    def axis_size(self, axes: Tuple[str, ...]) -> int:
        if self.mesh is None or not axes:
            return 1
        size = 1
        for a in axes:
            size *= self.mesh.shape[a]
        return size

    def hint(self, x, *spec):
        """with_sharding_constraint when a mesh is active, else identity."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    def hint_last(self, x, axes):
        """Constrain only the LAST dim; leading dims stay unconstrained so
        GSPMD keeps whatever batch/sequence sharding is flowing through
        (a full P(None,...,axes) would force replication on them)."""
        if self.mesh is None:
            return x
        spec = [P.UNCONSTRAINED] * (x.ndim - 1) + [axes]
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    def sharding(self, *spec) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*spec))


# Convenience singleton for local (single-device) execution.
LOCAL = Runtime()


def on_tpu() -> bool:
    """True on a TPU backend, where the Pallas kernels compile and the
    paged serving path runs them; elsewhere it runs the jnp references
    (which the CPU tests also use as the kernels' oracle)."""
    return jax.default_backend() == "tpu"


# Fixed, git-ignored home of the persistent compile cache when the
# environment names none.  The path is part of JAX's cache key, so it never
# carries a pid, a time or a temporary name.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory and no
    other is set here; otherwise the cache lives at ``COMPILE_CACHE_DIR``
    inside the checkout.  Entry points call this from ``main()``, never
    at import, so importing the package (the tests do) caches nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
