"""High-level adaptive-ICA estimator built on EASI + SMBGD.

This is the deployable API of the paper's system: model creation, training and
deployment in one object, supporting the *adaptive* (streaming / non-stationary)
regime the paper targets.

    ica = AdaptiveICA(EASIConfig(n_components=2, n_features=4),
                      SMBGDConfig(batch_size=8, mu=2e-3, beta=0.9, gamma=0.5))
    state = ica.init(key)
    state, Y = ica.fit(state, X)            # offline: one pass over X
    state, y = ica.partial_fit(state, x_batch)   # online: track drift
    Y = ica.transform(state, X_new)         # deployment: separate only

``AdaptiveICA`` is the back-compat name for ``repro.stream.separator.Separator``
— the unified front-end over the three epoch drivers (``sgd``,
``smbgd_sequential``, ``smbgd_batched``; ``"smbgd"`` is an accepted alias of
the batched form).  For many concurrent sessions use
``repro.stream.SeparatorBank``, which is this estimator vmapped over a leading
stream axis with a fused multi-stream Pallas kernel.

Everything is pure-functional (state in/state out) so it drops into pjit/scan.
Data-parallel fitting over a device mesh is provided by ``make_sharded_step``
which psums the weighted gradient across the batch axis — the gradient sum in
``batched_relative_gradient`` is linear in samples, so DP is exact.  (Stream
parallelism — sharding *sessions* rather than samples — lives in
``repro.stream.sharding``.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import easi as easi_lib
from repro.core import smbgd as smbgd_lib
from repro.core.easi import EASIConfig
from repro.core.smbgd import SMBGDConfig, SMBGDState
from repro.stream.separator import Separator


class AdaptiveICA(Separator):
    """Back-compat subclass; all behavior lives on ``Separator``."""


# ---------------------------------------------------------------------------
# Data-parallel fitting: exact DP because the weighted gradient sum is linear.
# Each device computes the weighted relative gradient over its local shard of
# the mini-batch; a psum makes the update identical to the single-device one
# (up to the within-batch β ordering, which DP reinterprets as interleaved
# sample order — recorded in DESIGN.md §6).
# ---------------------------------------------------------------------------


def make_sharded_step(mesh, easi_cfg: EASIConfig, cfg: SMBGDConfig, axis: str = "data"):
    """Build a pjit-able SMBGD step where the mini-batch is sharded over
    ``axis``.  Returns ``step(state, X_batch) -> (state, Y)``.

    Within-batch weights are computed over the *global* sample index so the
    sequential semantics match the single-device run when samples are
    contiguously sharded.
    """
    n_shards = mesh.shape[axis]
    P_global = cfg.batch_size
    if P_global % n_shards:
        raise ValueError(f"batch_size {P_global} not divisible by {n_shards} shards")

    def local_gradient(B, X_local, w_local):
        Y = X_local @ B.T
        S_local = easi_lib.batched_relative_gradient(Y, w_local, easi_cfg.g)
        # Σw·I was added per-shard; the psum then over-counts the identity —
        # no: batched_relative_gradient adds sum(w_local)·I locally, and
        # psum(Σ_shard sum(w_local)) = sum(w_global): exact.
        return jax.lax.psum(S_local, axis), Y

    def step(state: SMBGDState, X_batch: jnp.ndarray):
        w = cfg.within_batch_weights(dtype=state.B.dtype)

        sharded = jax.shard_map(
            local_gradient,
            mesh=mesh,
            in_specs=(P(None, None), P(axis, None), P(axis)),
            out_specs=(P(None, None), P(axis, None)),
            check_vma=False,
        )
        S, Y = sharded(state.B, X_batch, w)
        H_hat, B_next = smbgd_lib.smbgd_commit(
            state.step, state.H_hat, S, state.B, cfg
        )
        return SMBGDState(B=B_next, H_hat=H_hat, step=state.step + 1), Y

    return step
