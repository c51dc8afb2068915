"""Jit'd wrapper for the fused SMBGD commit kernel (padding + interpret switch)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.smbgd_update.smbgd_update import smbgd_update_pallas

_LANE = 128
_SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("interpret",))
def smbgd_update(
    gamma_hat: jnp.ndarray,
    H_prev: jnp.ndarray,
    S: jnp.ndarray,
    B: jnp.ndarray,
    *,
    interpret: bool | None = None,
):
    """Fused Ĥ/B commit for arbitrary (n, m); pads to sublane/lane alignment.

    Zero-padding is exact: padded rows/cols of Ĥ stay zero (γ̂·0 + 0) and the
    padded block of B is zero so Ĥ·B contributes nothing outside [:n, :m].
    """
    if interpret is None:
        interpret = interpret_mode()
    n, m = B.shape
    align = _SUBLANE if interpret else _LANE
    n_pad = _round_up(max(n, _SUBLANE), align)
    block_m = min(512, _round_up(max(m, _SUBLANE), align))
    m_pad = _round_up(m, block_m)
    Hp = jnp.zeros((n_pad, n_pad), H_prev.dtype).at[:n, :n].set(H_prev)
    Sp = jnp.zeros((n_pad, n_pad), S.dtype).at[:n, :n].set(S)
    Bp = jnp.zeros((n_pad, m_pad), B.dtype).at[:n, :m].set(B)
    g = jnp.asarray(gamma_hat, jnp.float32).reshape(1, 1)
    H_new, B_new = smbgd_update_pallas(
        g, Hp, Sp, Bp, block_m=block_m, interpret=interpret
    )
    return H_new[:n, :n], B_new[:n, :m]
