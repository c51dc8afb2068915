"""Pallas kernels for the compute hot spots the paper optimizes.

Lowering is chosen from the backend, in one place: the kernels run through
the Pallas interpreter if and only if JAX's default backend is the CPU, and
compile to Mosaic on a TPU.  Layouts (``easi_gradient.ops.bank_layout``) and
the autotune cache key (``stream.autotune.backend_tag``) follow the same
decision, so nothing can silently interpret on an accelerator.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True iff Pallas kernels must run in interpret mode (CPU backend)."""
    return jax.default_backend() == "cpu"
