"""Device parallelism over the stream axis of a SeparatorBank.

Streams are independent sessions, so sharding the bank over devices needs no
collectives in the hot path — each device steps its local slice of the bank
with the same fused program (``shard_map`` with everything partitioned over
the stream axis).  This is the "rack of FPGAs" layout: bank state and the
incoming mini-batches live sharded; only diagnostics ever gather.

Works for every bank flavour: the vmap paths, the PR-1 gradient-kernel path,
and the fused whole-step megakernel (``fused=True`` — persistent padded state
shards over its leading axis like any other; each device launches its own
``(local_streams, P-tiles)`` grid).  Per-stream ``BankHyperparams`` are
threaded through ``shard_map`` as explicit sharded operands (NOT closure
captures, which would silently replicate them and break the local shapes);
each device rebuilds a local-width bank around its slice.

The local banks inherit the parent's RESOLVED memory-system knobs
(``dtype_policy``, ``prefetch``, tile geometry) with ``autotune=False`` —
tuning keys on the global shape; per-device re-resolution against the
local stream count would silently pick a different (possibly untuned)
geometry on every device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.smbgd import BankHyperparams
from repro.stream.bank import BankState, SeparatorBank


def bank_sharding(mesh, axis: str = "stream") -> BankState:
    """NamedSharding pytree for a BankState: every leaf partitioned over
    ``axis`` on its leading (stream) dimension.  Feed to ``jax.device_put`` or
    ``Checkpointer.restore(shardings=...)`` for reshard-on-load.

    Expects a conv-bearing state (anything ``SeparatorBank.init`` produced);
    a legacy ``conv=None`` state has a different pytree structure — normalize
    it first with ``state._replace(conv=jnp.full((S,), jnp.inf))``."""
    return BankState(
        B=NamedSharding(mesh, P(axis)),
        H_hat=NamedSharding(mesh, P(axis)),
        step=NamedSharding(mesh, P(axis)),
        conv=NamedSharding(mesh, P(axis)),
        health=NamedSharding(mesh, P(axis)),
        moments=NamedSharding(mesh, P(axis)),
    )


def make_sharded_bank_step(
    bank: SeparatorBank, mesh, axis: str = "stream", donate: bool = False
):
    """Build a jitted ``step(state, X[, active]) -> (state, Y)`` where the
    bank's stream axis is sharded over mesh axis ``axis``.

    Each device runs the fused bank step on its local streams; there are no
    cross-device collectives (streams are independent).  Requires
    ``bank.n_streams %% mesh.shape[axis] == 0``.  ``donate=True`` donates the
    state buffers (persistent-padded fused banks: zero steady-state allocs
    per device).
    """
    n_dev = mesh.shape[axis]
    if bank.n_streams % n_dev:
        raise ValueError(
            f"n_streams {bank.n_streams} not divisible by {n_dev} devices on "
            f"axis {axis!r}"
        )
    local_streams = bank.n_streams // n_dev
    # Pin the parent bank's RESOLVED geometry on the local bank: autotune ran
    # (or was opted out) against the global (S, P, m, n) key, and the local
    # bank must not re-resolve against the local-S key (different entry) or
    # re-derive block_s from local_streams vs a cached global block_s that no
    # longer divides.  dtype_policy/prefetch ride along via replace().
    local_block_s = bank.block_s
    if local_block_s is not None and local_streams % local_block_s:
        local_block_s = None  # fall back to the derived default locally
    local_bank = dataclasses.replace(
        bank,
        n_streams=local_streams,
        hyperparams=None,
        block_p=bank.layout.block_p if bank.fused else bank.block_p,
        block_s=local_block_s,
        prefetch=bool(bank.prefetch),
        autotune=False,
    )
    hetero = bank.hyperparams is not None

    def local_step(B, H_hat, step, conv, X, active, hp):
        lb = local_bank
        if hetero:
            lb = dataclasses.replace(lb, hyperparams=BankHyperparams(*hp))
        st, Y = lb.step(BankState(B, H_hat, step, conv), X, active=active)
        return st.B, st.H_hat, st.step, st.conv, st.health, st.moments, Y

    hp_spec = (P(axis),) * 3 if hetero else ()
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), hp_spec),
        out_specs=(P(axis),) * 7,
        check_vma=False,
    )

    def step(
        state: BankState, X: jnp.ndarray, active: Optional[jnp.ndarray] = None
    ) -> Tuple[BankState, jnp.ndarray]:
        if active is None:
            active = jnp.ones((bank.n_streams,), dtype=bool)
        hp = tuple(bank.hyperparams) if hetero else ()
        conv = state.conv
        if conv is None:  # legacy states: normalize before entering shard_map
            conv = jnp.full((bank.n_streams,), jnp.inf, jnp.float32)
        B, H_hat, stp, conv, health, moments, Y = sharded(
            state.B, state.H_hat, state.step, conv, X, active, hp
        )
        return BankState(B, H_hat, stp, conv, health, moments), Y

    return jax.jit(step, donate_argnums=(0,) if donate else ())
