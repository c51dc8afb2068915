"""Public attention op: shape policy, padding, kernel/ref dispatch.

``attention(...)`` is the single entry point the model zoo calls.  It routes to
the Pallas flash kernel when shapes are tile-able (training/prefill) and to the
jnp reference otherwise (tiny smoke shapes, decode-with-cache fast path).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


def _use_pallas_default() -> bool:
    # Interpret-mode flash over 32k sequences is minutes-slow on CPU; default
    # to the XLA reference path on CPU and the kernel on real TPU.
    return os.environ.get("REPRO_USE_PALLAS_ATTN", "0") == "1"


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "window", "softcap", "use_pallas", "block_q", "block_k",
        "softmax_dtype",
    ),
)
def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    use_pallas: bool | None = None,
    block_q: int = 128,
    block_k: int = 128,
    softmax_dtype: str = "float32",
) -> jnp.ndarray:
    """Multi-head attention over (B, H, T, d) tensors; GQA via Hkv | Hq."""
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    B, Hq, Tq, d = q.shape
    Tk = k.shape[2]
    tileable = Tq % block_q == 0 and Tk % block_k == 0 and Tq >= block_q
    if not (use_pallas and tileable):
        return attention_ref(
            q, k, v, scale=scale, causal=causal, window=window, softcap=softcap,
            q_offset=Tk - Tq, softmax_dtype=jnp.dtype(softmax_dtype),
        )
    return flash_attention_pallas(
        q, k, v,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret_mode(),
    )
