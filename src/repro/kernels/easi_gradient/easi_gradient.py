"""Pallas TPU kernel: fused batched EASI relative gradient (the paper's datapath).

Computes, for ``Y (P, n)`` and within-batch SMBGD weights ``w (P,)``:

    S = (Σ_p w_p) I − Yᵀ W Y − Gᵀ W Y + (Gᵀ W Y)ᵀ,   G = g(Y),  W = diag(w)

in ONE pass over Y tiled along P: each grid step loads a ``(block_p, n)`` tile
into VMEM, evaluates the nonlinearity in-register (never materializing G in
HBM), performs the two weighted MXU matmuls, and accumulates the (n, n) result
in place.  This is the TPU-native replacement for the paper's one-sample-per-
clock FPGA pipeline: arithmetic intensity grows from O(1) (rank-1 outer-product
updates) to O(block_p) (rank-P matmuls) — MXU-bound instead of HBM-bound.

The *bank* variant (``easi_gradient_bank_pallas``) adds a leading **streams**
grid dimension: for ``Y (S, P, n)`` the grid is ``(S, P // block_p)`` and one
launch folds every stream's tiles — S independent separator sessions cost one
kernel dispatch instead of S.  The stream axis is the majormost grid dim, so
for each stream the tile index still iterates innermost and the per-stream
(n, n) accumulator pattern is unchanged.

The *whole-step* variant (``smbgd_step_bank_pallas``) is the megakernel: the
same ``(streams, P-tiles)`` grid, but each grid step also computes its tile of
``Y = X Bᵀ`` in VMEM (X never leaves the kernel as Y in HBM until the output
write), and each stream's LAST tile performs the SMBGD commit in-register:

``prefetch=True`` swaps the X operand's block pipeline for an explicit
double-buffered DMA: X stays in ``pl.ANY`` (HBM on TPU) and the kernel
overlaps the NEXT tile's ``make_async_copy`` with the CURRENT tile's gradient
fold — the paper's "compute never waits on memory" pipelining one level
deeper than BlockSpec auto-pipelining, with the prefetch window crossing
stream-block boundaries (the last tile of stream-block s prefetches tile 0 of
stream-block s+1, so the only un-overlapped DMA is the very first one).  The
synchronous path stays the fallback/oracle: on the interpret path the two are
bit-identical (tested), so prefetch is purely a memory-system knob.

Reduced-precision persistent state rides the same launches for free: the
kernels cast every operand to f32 at load (``.astype`` below) and back to the
output ref's dtype at commit, so a bank whose ``B``/``Ĥ`` live in bf16 (see
``ops.BankLayout.dtype_policy``) runs the gradient fold and the commit
accumulation entirely in f32 — casts happen ONLY at the load/commit
boundaries, and frozen (inactive) slots round-trip bf16→f32→bf16 exactly.
Every contraction runs at ``Precision.HIGHEST`` (``_dot``): a TPU otherwise
rounds f32 matmul operands to bf16, and the paper's datapath is f32.

    Ĥ' = γ̂·Ĥ + Σ_tiles S_tile      (γ̂ gated to 0 where step == 0)
    B' = B + Ĥ'·B ;  step' = step + 1

so one kernel dispatch per bank tick reads ``X, B, Ĥ, step, conv`` and writes
``Y, B', Ĥ', step', conv'`` — no intermediate ``Y``/``S_grad`` round-trips
HBM.  ``conv'`` is the per-stream convergence statistic ``‖Ĥ′B‖_F/‖B‖_F``
(relative update magnitude) folded from the commit's own ΔB, so the serving
layer's eviction policy reads an (S,)-float side channel instead of pulling
state matrices back to the host.
Per-stream weight rows ``W (S, P, 1)`` and momentum coefficients
``γ̂ (S, 1)`` make the bank heterogeneous (per-stream μ, β, γ) inside a single
launch, and ``active (S, 1)`` freezes evicted/idle slots in-kernel (their
``B``/``Ĥ``/``step`` are written back unchanged; their Y is still produced).
``block_s`` streams ride each grid cell as a leading batch dimension of every
block (batched ``dot_general``s inside the cell), so the grid is
``(S / block_s, P / block_p)`` — per-cell launch/loop overhead amortizes over
the stream block while the math stays per-stream independent.

Layout notes (TPU target; validated on CPU via interpret=True):
  * last dims (n for Y/Ĥ, m for X/B) are padded to a multiple of 128 (lane
    width) by ops.py — 8 (f32 sublane) in interpret mode,
  * block_p is a multiple of 8 (f32 sublane) — default 512,
  * accumulation in fp32 regardless of input dtype (preferred_element_type),
  * the whole-step kernel's gradient accumulator is a VMEM scratch buffer
    (``(n, n)`` fp32) that persists across the sequential grid: tiles iterate
    innermost, so it is re-initialized at each stream's tile 0 and consumed by
    the commit at tile T-1; ``B``/``Ĥ`` blocks are revisited (index map pins
    them per stream) and written once, on the commit tile,
  * per-stream scalars (``step``, ``γ̂``, ``active``, ``conv`` and the
    health/moment outputs) ride as ``(block_s, 1)`` / ``(block_s, 2)`` VMEM
    blocks, so Mosaic's sublane rule requires ``block_s`` to be a multiple
    of 8 or the whole bank (``ops.default_block_s`` picks only those),
  * every per-stream reduction keeps its rank (``_reduce_rows``): Mosaic
    aborts on a two-axis reduction to a rank-1 vector,
  * zero padding is exact end-to-end: padded m-columns of X/B keep padded Y
    zero (g(0) = 0 for every registered nonlinearity), padded w rows add
    nothing, and the only nonzero the commit writes into the padded region is
    the Σw diagonal of the identity term, which stays confined there (padded
    rows of B are zero, so it never couples back into the logical block —
    persistent padded state does not need re-zeroing between ticks).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nonlinearities import NONLINEARITIES

# The kernel nonlinearity table IS the core registry: every g(.) there is pure
# jnp elementwise (VPU-lowerable), so registering a new nonlinearity in
# core/nonlinearities.py makes it available inside the kernel automatically —
# the two banks cannot drift.
NONLIN_KERNELS: dict = NONLINEARITIES

# Per-stream health word: an int32 bitmask folded in-register at commit time
# (one more reduction riding the conv statistic's pass — no extra HBM
# traffic).  0 means healthy; any set bit means the tick's commit was REFUSED
# for that stream (the slot keeps its pre-tick B/Ĥ/step/conv, exactly like
# the active-mask freeze) and the serving layer decides rollback/quarantine.
HEALTH_OK = 0
HEALTH_NONFINITE_B = 1 << 0  # B' picked up a NaN/Inf
HEALTH_NONFINITE_H = 1 << 1  # Ĥ' picked up a NaN/Inf
HEALTH_NONFINITE_Y = 1 << 2  # some Y tile was non-finite (bad input block)
HEALTH_BLOWUP = 1 << 3  # ‖Ĥ′B‖/‖B‖ above the static blow-up bound

# Static blow-up bound on the relative update magnitude ‖ΔB‖_F/‖B‖_F.  A
# legitimate SMBGD tick moves B by a few percent (early ticks by O(1) at
# most); the divergent μ-regime of online ICA (arXiv:1710.05384) multiplies
# B in a handful of ticks — 100 is far above any converging trajectory and
# far below a blow-up's second tick.
HEALTH_BLOWUP_BOUND = 100.0

# Per-stream moment telemetry: raw sums [Σy², Σy⁴] over the stream's whole Y
# block, folded tile-by-tile in the same in-register reduction pass as conv
# and the health word (Y never re-read from HBM; the only cost is one (S, 2)
# f32 output leaf — 8 bytes/stream/tick).  The serving layer turns the sums
# into a kurtosis estimate κ = N·Σy⁴/(Σy²)² (N = logical P·n, known to the
# host) and drives the moment-scaled adaptive μ controller from it
# (arXiv:2509.15127: learning rate ∝ 1/high-order moments).  Padding-exact:
# padded Y entries are exactly zero and contribute nothing to either sum.
MOMENT_LEAVES = 2  # [Σy², Σy⁴]

# Scoped VMEM the step and probe megakernels may use (Mosaic's
# ``vmem_limit_bytes``).  TPU v5e holds 128 MiB of VMEM but its compiler
# scopes a kernel to 16 MiB unless told otherwise.  64 MiB lets a bank of 36
# paper-shape sessions, a width that is not a multiple of 8, run as one
# stream-block, and a ``block_p=512`` layout (about 5.2 MiB per stream at
# HIGHEST precision) still take 8 streams per block.  ``ops.default_block_s``
# budgets against this number.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _reduce_rows(op, a):
    """Reduce a ``(bs, r, c)`` block to ``(bs, 1)`` with ``op`` (``jnp.sum``
    or ``jnp.any``): lanes first, then sublanes, each with ``keepdims``.  The
    one-shot ``op(a, axis=(1, 2))[:, None]`` form aborts the TPU compiler
    (Mosaic's layout check on the rank-1 intermediate), so every per-stream
    reduction in these kernels goes through here — and the vmap reference
    (``stream.bank``) reduces in the same order."""
    return op(op(a, axis=2, keepdims=True), axis=1)


def _dot(a, b, dims):
    """f32 ``dot_general`` at full precision.  On a TPU the default rounds
    f32 operands to bf16, and the rounding grows fast in a separator near
    its blow-up bound.  HIGHEST costs VMEM for the split operands, which
    ``ops._resident_bytes_per_stream`` counts; the interpreter computes in
    f32 either way."""
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _health_word(b_new, h_new, ybad, delta, blowup: float):
    """Fold the per-stream health bitmask from commit-time registers:
    ``b_new``/``h_new`` (bs, n, ·) f32, ``ybad`` (bs, 1) int (nonzero where
    some Y tile was non-finite), ``delta`` (bs, 1) the conv statistic.
    ``~(delta <= blowup)`` deliberately catches NaN deltas too."""
    i32 = jnp.int32
    bbad = _reduce_rows(jnp.any, ~jnp.isfinite(b_new))
    hbad = _reduce_rows(jnp.any, ~jnp.isfinite(h_new))
    blow = ~(delta <= blowup)
    return (
        bbad.astype(i32) * HEALTH_NONFINITE_B
        + hbad.astype(i32) * HEALTH_NONFINITE_H
        + (ybad != 0).astype(i32) * HEALTH_NONFINITE_Y
        + blow.astype(i32) * HEALTH_BLOWUP
    )


def _fold_tile(y, w, nonlin: str):
    """Fold one (bp, n) fp32 tile of Y into an (n, n) gradient contribution."""
    g = NONLIN_KERNELS[nonlin](y)
    yw = y * w  # weighted rows — one VPU pass
    # Two MXU contractions over the tile's P dimension (rank-bp updates).
    gram = _dot(y, yw, (((0,), (0,)), ((), ())))  # Yᵀ W Y  (n, n)
    cross = _dot(g, yw, (((0,), (0,)), ((), ())))  # Gᵀ W Y  (n, n)
    n = gram.shape[0]
    # Per-tile identity contribution: Σ_tiles sum(w_tile)·I == sum(w)·I overall.
    eye = jnp.eye(n, dtype=jnp.float32) * jnp.sum(w)
    return eye - gram - cross + cross.T


def _easi_gradient_kernel(y_ref, w_ref, out_ref, *, nonlin: str):
    """One grid step: fold a (block_p, n) tile of Y into the (n, n) accumulator."""
    i = pl.program_id(0)
    y = y_ref[...].astype(jnp.float32)  # (bp, n)
    w = w_ref[...].astype(jnp.float32)  # (bp, 1)
    s_tile = _fold_tile(y, w, nonlin)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = s_tile

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += s_tile


def easi_gradient_pallas(
    Y: jnp.ndarray,
    w: jnp.ndarray,
    *,
    nonlinearity: str = "cubic",
    block_p: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Launch the fused gradient kernel.  Expects pre-padded inputs:
    ``Y (P, n)`` with P % block_p == 0 and n lane-aligned; ``w (P, 1)``.
    Returns ``S (n, n)`` in fp32."""
    P, n = Y.shape
    assert P % block_p == 0, (P, block_p)
    grid = (P // block_p,)
    kernel = functools.partial(_easi_gradient_kernel, nonlin=nonlinearity)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_p, n), lambda i: (i, 0)),
            pl.BlockSpec((block_p, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(Y, w)


def _easi_gradient_bank_kernel(y_ref, w_ref, out_ref, *, nonlin: str):
    """One grid step of the bank kernel: fold stream s's tile i into its
    (n, n) accumulator.  Grid is (streams, tiles); tiles iterate innermost so
    ``i == 0`` marks the first visit to stream s's output block."""
    i = pl.program_id(1)
    y = y_ref[0].astype(jnp.float32)  # (bp, n) — block is (1, bp, n)
    w = w_ref[...].astype(jnp.float32)  # (bp, 1) — shared across streams
    s_tile = _fold_tile(y, w, nonlin)

    @pl.when(i == 0)
    def _init():
        out_ref[0] = s_tile

    @pl.when(i > 0)
    def _acc():
        out_ref[0] += s_tile


def easi_gradient_bank_pallas(
    Y: jnp.ndarray,
    w: jnp.ndarray,
    *,
    nonlinearity: str = "cubic",
    block_p: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Batched-stream launch: ``Y (S, P, n)``, shared weights ``w (P, 1)`` →
    ``S_out (S, n, n)`` fp32.  One kernel dispatch folds all S·(P/block_p)
    tiles via the (streams, tiles) grid.  Expects pre-padded inputs as in
    ``easi_gradient_pallas``."""
    S, P, n = Y.shape
    assert P % block_p == 0, (P, block_p)
    grid = (S, P // block_p)
    kernel = functools.partial(_easi_gradient_bank_kernel, nonlin=nonlinearity)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_p, n), lambda s, i: (s, i, 0)),
            pl.BlockSpec((block_p, 1), lambda s, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, n), lambda s, i: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, n, n), jnp.float32),
        interpret=interpret,
    )(Y, w)


def _fold_tile_batched(y, w, nonlin: str):
    """Batched ``_fold_tile``: fold a (bs, bp, n) block of Y tiles — one per
    stream in the stream-block — into (bs, n, n) gradient contributions."""
    g = NONLIN_KERNELS[nonlin](y)
    yw = y * w  # (bs, bp, n) * (bs, bp, 1)
    dims = (((1,), (1,)), ((0,), (0,)))  # contract bp, batch over streams
    gram = _dot(y, yw, dims)
    cross = _dot(g, yw, dims)
    n = gram.shape[-1]
    eye = jnp.eye(n, dtype=jnp.float32)[None] * jnp.sum(w, axis=1, keepdims=True)
    return eye - gram - cross + cross.transpose(0, 2, 1)


def _commit_streams(
    b,
    h_ref,
    step_ref,
    gamma_hat_ref,
    active_ref,
    conv_ref,
    b_out_ref,
    h_out_ref,
    step_out_ref,
    conv_out_ref,
    health_out_ref,
    moment_out_ref,
    acc_ref,
    ybad_ref,
    mom_ref,
    *,
    with_health: bool,
    with_moments: bool,
    blowup: float,
):
    """The SMBGD commit tail shared by the sync and prefetch step kernels:
    fold the accumulated gradient into ``Ĥ'``/``B'``/``step'``/``conv'`` for
    one stream-block.  ``b`` is the block's B already cast to f32; all math
    runs in f32 and casts back to the output refs' (storage) dtype only at
    the final writes — frozen slots round-trip bf16→f32→bf16 exactly.

    ``with_health=True`` additionally folds the per-stream health bitmask
    (``_health_word``) and REFUSES the commit for unhealthy streams: their
    slots keep the pre-tick B/Ĥ/step/conv exactly like the active-mask
    freeze, so one poisoned input block can never contaminate persistent
    state.  ``with_health=False`` writes health 0 and commits on ``active``
    alone (the pre-containment behaviour; kept as the overhead baseline).

    ``with_moments=True`` publishes the cross-tile moment fold (``mom_ref``,
    per-stream [Σy², Σy⁴]) for the streams actually served this tick; like
    health it is a fresh per-tick verdict — frozen slots report 0 and
    ``with_moments=False`` writes zeros.  The moment write is observational
    only: B'/Ĥ'/step'/conv'/health' are bit-identical with moments on or
    off."""
    step = step_ref[...]  # (bs, 1)
    active = active_ref[...] != 0  # (bs, 1)
    # the paper's first-batch rule, per stream: γ̂ gated off at step 0
    gamma_hat = jnp.where(step == 0, 0.0, gamma_hat_ref[...])[:, :, None]
    h_prev = h_ref[...].astype(jnp.float32)  # (bs, n, n)
    h_new = gamma_hat * h_prev + acc_ref[...]
    db = _dot(h_new, b, (((2,), (1,)), ((0,), (0,))))  # ΔB = Ĥ′B (bs, n, m)
    b_new = b + db
    # per-stream convergence statistic ‖ΔB‖_F / ‖B‖_F, in-register — no
    # extra HBM round-trip.  Padding-exact: padded rows/cols of B are
    # zero, so the padded Σw diagonal of Ĥ′ never reaches ΔB.
    num = jnp.sqrt(_reduce_rows(jnp.sum, db * db))  # (bs, 1)
    den = jnp.sqrt(_reduce_rows(jnp.sum, b * b))
    delta = num / jnp.maximum(den, 1e-12)  # (bs, 1)
    conv_prev = conv_ref[...].astype(jnp.float32)  # (bs, 1)
    if with_health:
        health = _health_word(b_new, h_new, ybad_ref[...], delta, blowup)
        commit = active & (health == 0)  # (bs, 1)
        # frozen slots report 0: health is a fresh per-tick verdict on the
        # streams that were actually served, not a carried statistic
        health_out_ref[...] = jnp.where(active, health, 0)
    else:
        commit = active
        health_out_ref[...] = jnp.zeros_like(health_out_ref)
    if with_moments:
        # (bs, 1) active mask broadcasts over the (bs, 2) [Σy², Σy⁴] fold
        moment_out_ref[...] = jnp.where(active, mom_ref[...], 0.0)
    else:
        moment_out_ref[...] = jnp.zeros_like(moment_out_ref)
    commit3 = commit[:, :, None]  # (bs, 1, 1)
    h_out_ref[...] = jnp.where(commit3, h_new, h_prev).astype(h_out_ref.dtype)
    b_out_ref[...] = jnp.where(commit3, b_new, b).astype(b_out_ref.dtype)
    step_out_ref[...] = step + jnp.where(commit, 1, 0).astype(step.dtype)
    conv_out_ref[...] = jnp.where(commit, delta, conv_prev)


def _fold_ybad_tile(y, ybad_ref, i, with_health: bool):
    """OR this tile's per-stream "Y went non-finite" flag into the (bs, 1)
    int32 scratch — the cross-tile leg of the health reduction.  A trace-time
    no-op when health is off (``with_health`` is static)."""
    if not with_health:
        return
    # Σ(y·0) is NaN iff the tile holds any non-finite (Inf·0 = NaN·0 = NaN)
    # and exactly 0 otherwise — no finite-overflow corner, and one multiply +
    # one reduction instead of the isfinite/not/any triple pass.
    marker = _reduce_rows(jnp.sum, y * 0.0)  # (bs, 1)
    ybad = (~(marker == 0.0)).astype(jnp.int32)

    @pl.when(i == 0)
    def _ybad_init():
        ybad_ref[...] = ybad

    @pl.when(i > 0)
    def _ybad_acc():
        ybad_ref[...] = ybad_ref[...] | ybad


def _fold_moment_tile(y, mom_ref, i, with_moments: bool):
    """Accumulate this tile's per-stream raw moments [Σy², Σy⁴] into the
    (bs, 2) f32 scratch — the cross-tile leg of the kurtosis reduction, a
    third reduction riding the same Y registers as conv and the health fold.
    A trace-time no-op when moments are off (``with_moments`` is static)."""
    if not with_moments:
        return
    y2 = y * y  # one VPU square; y⁴ = (y²)² reuses it
    mom = jnp.concatenate(
        [_reduce_rows(jnp.sum, y2), _reduce_rows(jnp.sum, y2 * y2)], axis=-1
    )  # (bs, 2)

    @pl.when(i == 0)
    def _mom_init():
        mom_ref[...] = mom

    @pl.when(i > 0)
    def _mom_acc():
        mom_ref[...] += mom


def _smbgd_step_bank_kernel(
    x_ref,
    w_ref,
    b_ref,
    h_ref,
    step_ref,
    gamma_hat_ref,
    active_ref,
    conv_ref,
    y_ref,
    b_out_ref,
    h_out_ref,
    step_out_ref,
    conv_out_ref,
    health_out_ref,
    moment_out_ref,
    acc_ref,
    ybad_ref,
    mom_ref,
    *,
    nonlin: str,
    n_tiles: int,
    with_health: bool,
    with_moments: bool,
    blowup: float,
):
    """One grid step of the whole-step megakernel (grid = (stream-blocks,
    tiles): each cell carries ``block_s`` streams as a batch dimension).

    Every tile: Y-tile batch-matmul + nonlinearity + weighted gradient fold
    into the VMEM scratch accumulator (plus, with health on, the Y-finite
    flag fold).  The stream-block's last tile additionally commits the SMBGD
    update and writes ``B'``/``Ĥ'``/``step'``/``health'`` for its streams.
    """
    i = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (bs, bp, m)
    b = b_ref[...].astype(jnp.float32)  # (bs, n, m)
    # (bs, bp, n) — these streams' Y tiles, never re-read from HBM
    y = _dot(x, b, (((2,), (2,)), ((0,), (0,))))
    y_ref[...] = y.astype(y_ref.dtype)
    w = w_ref[...].astype(jnp.float32)  # (bs, bp, 1) — per-stream weight rows
    s_tile = _fold_tile_batched(y, w, nonlin)
    _fold_ybad_tile(y, ybad_ref, i, with_health)
    _fold_moment_tile(y, mom_ref, i, with_moments)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = s_tile

    @pl.when(i > 0)
    def _acc():
        acc_ref[...] += s_tile

    @pl.when(i == n_tiles - 1)
    def _commit():
        _commit_streams(
            b, h_ref, step_ref, gamma_hat_ref, active_ref, conv_ref,
            b_out_ref, h_out_ref, step_out_ref, conv_out_ref, health_out_ref,
            moment_out_ref, acc_ref, ybad_ref, mom_ref,
            with_health=with_health, with_moments=with_moments, blowup=blowup,
        )


def _x_tile_dma(x_hbm, xbuf_ref, sem_ref, slot, t, n_tiles, block_s, block_p):
    """Async-copy descriptor for global tile ``t``'s X block (stream-block
    ``t // n_tiles``, tile ``t % n_tiles``) into double-buffer ``slot``."""
    sb = t // n_tiles
    i = jax.lax.rem(t, n_tiles)
    return pltpu.make_async_copy(
        x_hbm.at[
            pl.ds(sb * block_s, block_s), pl.ds(i * block_p, block_p), :
        ],
        xbuf_ref.at[slot],
        sem_ref.at[slot],
    )


def _smbgd_step_bank_kernel_prefetch(
    x_hbm,
    w_ref,
    b_ref,
    h_ref,
    step_ref,
    gamma_hat_ref,
    active_ref,
    conv_ref,
    y_ref,
    b_out_ref,
    h_out_ref,
    step_out_ref,
    conv_out_ref,
    health_out_ref,
    moment_out_ref,
    acc_ref,
    ybad_ref,
    mom_ref,
    xbuf_ref,
    sem_ref,
    *,
    nonlin: str,
    n_tiles: int,
    n_sblocks: int,
    block_s: int,
    block_p: int,
    with_health: bool,
    with_moments: bool,
    blowup: float,
):
    """Double-buffered variant of ``_smbgd_step_bank_kernel``: X rides in
    ``pl.ANY`` (HBM) and each grid step starts the NEXT tile's DMA before
    folding the CURRENT tile, alternating two VMEM buffers.  The prefetch
    window runs over the GLOBAL tile counter ``t = sb·n_tiles + i``, so it
    crosses stream-block boundaries — only tile 0 of the whole launch pays an
    un-overlapped DMA.  Everything downstream of the X load is byte-for-byte
    the synchronous kernel (bit-identity on the interpret path is tested)."""
    sb = pl.program_id(0)
    i = pl.program_id(1)
    t = sb * n_tiles + i  # global tile counter — the prefetch clock
    total = n_sblocks * n_tiles

    def dma(slot, t_idx):
        return _x_tile_dma(
            x_hbm, xbuf_ref, sem_ref, slot, t_idx, n_tiles, block_s, block_p
        )

    @pl.when(t == 0)
    def _warmup():  # the one DMA nothing can hide
        dma(0, 0).start()

    @pl.when(t + 1 < total)
    def _prefetch_next():  # overlap the next tile's DMA with this fold
        dma(jax.lax.rem(t + 1, 2), t + 1).start()

    dma(jax.lax.rem(t, 2), t).wait()
    x = xbuf_ref[jax.lax.rem(t, 2)].astype(jnp.float32)  # (bs, bp, m)
    b = b_ref[...].astype(jnp.float32)  # (bs, n, m)
    y = _dot(x, b, (((2,), (2,)), ((0,), (0,))))
    y_ref[...] = y.astype(y_ref.dtype)
    w = w_ref[...].astype(jnp.float32)
    s_tile = _fold_tile_batched(y, w, nonlin)
    _fold_ybad_tile(y, ybad_ref, i, with_health)
    _fold_moment_tile(y, mom_ref, i, with_moments)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = s_tile

    @pl.when(i > 0)
    def _acc():
        acc_ref[...] += s_tile

    @pl.when(i == n_tiles - 1)
    def _commit():
        _commit_streams(
            b, h_ref, step_ref, gamma_hat_ref, active_ref, conv_ref,
            b_out_ref, h_out_ref, step_out_ref, conv_out_ref, health_out_ref,
            moment_out_ref, acc_ref, ybad_ref, mom_ref,
            with_health=with_health, with_moments=with_moments, blowup=blowup,
        )


def _smbgd_probe_bank_kernel(
    x_ref,
    w_ref,
    b_ref,
    h_ref,
    step_ref,
    gamma_hat_ref,
    active_ref,
    conv_ref,
    conv_out_ref,
    health_out_ref,
    moment_out_ref,
    acc_ref,
    ybad_ref,
    mom_ref,
    *,
    nonlin: str,
    n_tiles: int,
    with_health: bool,
    with_moments: bool,
    blowup: float,
):
    """Freeze-only probe variant of the megakernel: same ``(stream-blocks,
    tiles)`` grid and the same per-tile math (Y-tile batch-matmul +
    nonlinearity + weighted gradient fold), but the last tile computes ONLY
    the convergence statistic the commit WOULD produce — ``‖Ĥ′B‖_F/‖B‖_F``
    from the virtual ``Ĥ′ = γ̂Ĥ + S`` — and writes nothing else.  No ``Y``,
    ``B'``, ``Ĥ'`` or ``step'`` ever reach HBM: the out-of-band drift probe
    of thousands of parked (frozen) separators is one (S,)-float launch."""
    i = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (bs, bp, m)
    b = b_ref[...].astype(jnp.float32)  # (bs, n, m)
    # (bs, bp, n) — stays in VMEM; probes never publish Y
    y = _dot(x, b, (((2,), (2,)), ((0,), (0,))))
    w = w_ref[...].astype(jnp.float32)  # (bs, bp, 1)
    s_tile = _fold_tile_batched(y, w, nonlin)
    _fold_ybad_tile(y, ybad_ref, i, with_health)
    _fold_moment_tile(y, mom_ref, i, with_moments)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = s_tile

    @pl.when(i > 0)
    def _acc():
        acc_ref[...] += s_tile

    @pl.when(i == n_tiles - 1)
    def _probe():
        _probe_streams(
            b, h_ref, step_ref, gamma_hat_ref, active_ref, conv_ref,
            conv_out_ref, health_out_ref, moment_out_ref,
            acc_ref, ybad_ref, mom_ref,
            with_health=with_health, with_moments=with_moments, blowup=blowup,
        )


def _probe_streams(
    b,
    h_ref,
    step_ref,
    gamma_hat_ref,
    active_ref,
    conv_ref,
    conv_out_ref,
    health_out_ref,
    moment_out_ref,
    acc_ref,
    ybad_ref,
    mom_ref,
    *,
    with_health: bool,
    with_moments: bool,
    blowup: float,
):
    """The freeze-only probe tail shared by the sync and prefetch probe
    kernels: the conv statistic a commit WOULD produce, and nothing else.
    ``with_health`` additionally reports the health word that commit WOULD
    have raised (from the virtual ``B' = B + ΔB``) — quarantined sessions
    are probed for sanity through the same launch that probes parked ones
    for drift."""
    step = step_ref[...]  # (bs, 1)
    active = active_ref[...] != 0  # (bs, 1)
    gamma_hat = jnp.where(step == 0, 0.0, gamma_hat_ref[...])[:, :, None]
    h_new = gamma_hat * h_ref[...].astype(jnp.float32) + acc_ref[...]
    # virtual ΔB = Ĥ′B (bs, n, m) — computed, never committed
    db = _dot(h_new, b, (((2,), (1,)), ((0,), (0,))))
    num = jnp.sqrt(_reduce_rows(jnp.sum, db * db))  # (bs, 1)
    den = jnp.sqrt(_reduce_rows(jnp.sum, b * b))
    delta = num / jnp.maximum(den, 1e-12)  # (bs, 1)
    conv_prev = conv_ref[...].astype(jnp.float32)
    if with_health:
        health = _health_word(b + db, h_new, ybad_ref[...], delta, blowup)
        health_out_ref[...] = jnp.where(active, health, 0)
    else:
        health_out_ref[...] = jnp.zeros_like(health_out_ref)
    if with_moments:
        moment_out_ref[...] = jnp.where(active, mom_ref[...], 0.0)
    else:
        moment_out_ref[...] = jnp.zeros_like(moment_out_ref)
    conv_out_ref[...] = jnp.where(active, delta, conv_prev)


def _smbgd_probe_bank_kernel_prefetch(
    x_hbm,
    w_ref,
    b_ref,
    h_ref,
    step_ref,
    gamma_hat_ref,
    active_ref,
    conv_ref,
    conv_out_ref,
    health_out_ref,
    moment_out_ref,
    acc_ref,
    ybad_ref,
    mom_ref,
    xbuf_ref,
    sem_ref,
    *,
    nonlin: str,
    n_tiles: int,
    n_sblocks: int,
    block_s: int,
    block_p: int,
    with_health: bool,
    with_moments: bool,
    blowup: float,
):
    """Double-buffered variant of ``_smbgd_probe_bank_kernel`` — the same
    global-tile-counter prefetch window as the step kernel's prefetch
    variant, with the freeze-only probe tail (no ``Y``/state writes)."""
    sb = pl.program_id(0)
    i = pl.program_id(1)
    t = sb * n_tiles + i
    total = n_sblocks * n_tiles

    def dma(slot, t_idx):
        return _x_tile_dma(
            x_hbm, xbuf_ref, sem_ref, slot, t_idx, n_tiles, block_s, block_p
        )

    @pl.when(t == 0)
    def _warmup():
        dma(0, 0).start()

    @pl.when(t + 1 < total)
    def _prefetch_next():
        dma(jax.lax.rem(t + 1, 2), t + 1).start()

    dma(jax.lax.rem(t, 2), t).wait()
    x = xbuf_ref[jax.lax.rem(t, 2)].astype(jnp.float32)  # (bs, bp, m)
    b = b_ref[...].astype(jnp.float32)  # (bs, n, m)
    y = _dot(x, b, (((2,), (2,)), ((0,), (0,))))
    w = w_ref[...].astype(jnp.float32)
    s_tile = _fold_tile_batched(y, w, nonlin)
    _fold_ybad_tile(y, ybad_ref, i, with_health)
    _fold_moment_tile(y, mom_ref, i, with_moments)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = s_tile

    @pl.when(i > 0)
    def _acc():
        acc_ref[...] += s_tile

    @pl.when(i == n_tiles - 1)
    def _probe():
        _probe_streams(
            b, h_ref, step_ref, gamma_hat_ref, active_ref, conv_ref,
            conv_out_ref, health_out_ref, moment_out_ref,
            acc_ref, ybad_ref, mom_ref,
            with_health=with_health, with_moments=with_moments, blowup=blowup,
        )


def smbgd_probe_bank_pallas(
    X: jnp.ndarray,
    W: jnp.ndarray,
    B: jnp.ndarray,
    H_hat: jnp.ndarray,
    step: jnp.ndarray,
    gamma_hat: jnp.ndarray,
    active: jnp.ndarray,
    conv: jnp.ndarray,
    *,
    nonlinearity: str = "cubic",
    block_p: int = 512,
    block_s: int = 1,
    interpret: bool = True,
    prefetch: bool = False,
    health: bool = True,
    moments: bool = False,
    blowup: float = HEALTH_BLOWUP_BOUND,
):
    """Batched virtual-conv probe: ONE launch over frozen bank state.

    Same pre-padded persistent-layout contract as ``smbgd_step_bank_pallas``
    but the only outputs are ``conv' (S, 1)`` — the per-stream statistic a
    commit would have produced (``conv`` carried through for masked-out
    streams) — ``health' (S, 1)`` int32, the health word that commit
    would have raised (0 when ``health=False`` or for masked-out streams),
    and ``moments' (S, 2)`` f32, the raw [Σy², Σy⁴] fold over the probe's Y
    (0 when ``moments=False`` or for masked-out streams).  The state
    operands are read-only: probing never mutates the frozen separators.
    ``prefetch=True`` double-buffers the X tile DMA (see the step kernel's
    prefetch notes; bit-identical on the interpret path).
    """
    S, P, m = X.shape
    n = B.shape[1]
    assert P % block_p == 0, (P, block_p)
    assert S % block_s == 0, (S, block_s)
    assert B.shape == (S, n, m) and H_hat.shape == (S, n, n)
    n_tiles = P // block_p
    bs = block_s
    n_sblocks = S // bs
    common_specs = [
        pl.BlockSpec((bs, block_p, 1), lambda s, i: (s, i, 0)),
        pl.BlockSpec((bs, n, m), lambda s, i: (s, 0, 0)),
        pl.BlockSpec((bs, n, n), lambda s, i: (s, 0, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
    ]
    if prefetch:
        kernel = functools.partial(
            _smbgd_probe_bank_kernel_prefetch,
            nonlin=nonlinearity, n_tiles=n_tiles, n_sblocks=n_sblocks,
            block_s=bs, block_p=block_p, with_health=health,
            with_moments=moments, blowup=blowup,
        )
        x_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [
            pltpu.VMEM((bs, n, n), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.int32),  # cross-tile Y-finite fold
            pltpu.VMEM((bs, MOMENT_LEAVES), jnp.float32),  # [Σy², Σy⁴] fold
            pltpu.VMEM((2, bs, block_p, m), X.dtype),  # the double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        kernel = functools.partial(
            _smbgd_probe_bank_kernel, nonlin=nonlinearity, n_tiles=n_tiles,
            with_health=health, with_moments=moments, blowup=blowup,
        )
        x_spec = pl.BlockSpec((bs, block_p, m), lambda s, i: (s, i, 0))
        scratch = [
            pltpu.VMEM((bs, n, n), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.int32),
            pltpu.VMEM((bs, MOMENT_LEAVES), jnp.float32),
        ]
    return pl.pallas_call(
        kernel,
        grid=(n_sblocks, n_tiles),
        in_specs=[x_spec] + common_specs,
        out_specs=[
            pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
            pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
            pl.BlockSpec((bs, MOMENT_LEAVES), lambda s, i: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
            jax.ShapeDtypeStruct((S, MOMENT_LEAVES), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **_compiler_params(prefetch),
    )(X, W, B, H_hat, step, gamma_hat, active, conv)


def _compiler_params(prefetch: bool) -> dict:
    """``pallas_call`` kwargs shared by the step and probe megakernels: the
    explicit VMEM limit the default ``block_s`` is budgeted against
    (``ops.default_block_s``), and — for the prefetch kernels, whose
    global-tile prefetch window threads DMA state across grid cells — BOTH
    grid dimensions sequential ("arbitrary", never "parallel": Mosaic must
    not megacore-split the grid).  The interpreter ignores both."""
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary") if prefetch else None,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        )
    }


def smbgd_step_bank_pallas(
    X: jnp.ndarray,
    W: jnp.ndarray,
    B: jnp.ndarray,
    H_hat: jnp.ndarray,
    step: jnp.ndarray,
    gamma_hat: jnp.ndarray,
    active: jnp.ndarray,
    conv: jnp.ndarray,
    *,
    nonlinearity: str = "cubic",
    block_p: int = 512,
    block_s: int = 1,
    interpret: bool = True,
    prefetch: bool = False,
    health: bool = True,
    moments: bool = False,
    blowup: float = HEALTH_BLOWUP_BOUND,
):
    """Whole-step fused SMBGD bank tick: ONE ``(stream-blocks, P-tiles)``
    launch.

    Expects pre-padded persistent-layout inputs (see ops.bank_layout):
    ``X (S, P, m)``, ``W (S, P, 1)``, ``B (S, n, m)``, ``H_hat (S, n, n)``,
    ``step (S, 1) int32``, ``gamma_hat (S, 1) f32``, ``active (S, 1) int32``,
    ``conv (S, 1) f32`` (previous per-stream convergence statistic — carried
    through unchanged for frozen streams).  ``block_s`` streams ride one grid
    cell as a batch dimension (S % block_s == 0) — per-stream math is
    independent, so the result is block_s invariant; larger blocks amortize
    per-cell grid overhead.  ``prefetch=True`` replaces the X BlockSpec
    pipeline with an explicit double-buffered ``make_async_copy`` from
    ``pl.ANY`` — overlapping the next tile's DMA with the current fold —
    and is bit-identical on the interpret path (tested).  ``B``/``H_hat``
    may live in a reduced-precision storage dtype (bf16): the kernel casts
    to f32 at load, accumulates the gradient and the commit in f32, and
    casts back only at the output writes.  Returns ``(Y (S, P, n), B',
    H_hat', step', conv', health', moments')`` — the full next bank state
    plus outputs, with no intermediate tensors materialized in HBM;
    ``conv'`` is the relative update magnitude ``‖Ĥ′B‖_F/‖B‖_F`` computed
    at commit time, ``health' (S, 1)`` int32 is the per-stream fault bitmask
    (see ``_health_word``; all-zero when ``health=False``), and
    ``moments' (S, 2)`` f32 is the raw [Σy², Σy⁴] per-stream fold over this
    tick's Y (all-zero when ``moments=False`` or for frozen slots; purely
    observational — every other output is bit-identical with moments on or
    off).  With ``health=True`` an unhealthy stream's commit is REFUSED
    in-kernel: its slot keeps the pre-tick state exactly like an
    ``active``-masked stream.
    """
    S, P, m = X.shape
    n = B.shape[1]
    assert P % block_p == 0, (P, block_p)
    assert S % block_s == 0, (S, block_s)
    assert B.shape == (S, n, m) and H_hat.shape == (S, n, n)
    n_tiles = P // block_p
    bs = block_s
    n_sblocks = S // bs
    common_specs = [
        pl.BlockSpec((bs, block_p, 1), lambda s, i: (s, i, 0)),
        pl.BlockSpec((bs, n, m), lambda s, i: (s, 0, 0)),
        pl.BlockSpec((bs, n, n), lambda s, i: (s, 0, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
        pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
    ]
    if prefetch:
        kernel = functools.partial(
            _smbgd_step_bank_kernel_prefetch,
            nonlin=nonlinearity, n_tiles=n_tiles, n_sblocks=n_sblocks,
            block_s=bs, block_p=block_p, with_health=health,
            with_moments=moments, blowup=blowup,
        )
        x_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [
            pltpu.VMEM((bs, n, n), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.int32),  # cross-tile Y-finite fold
            pltpu.VMEM((bs, MOMENT_LEAVES), jnp.float32),  # [Σy², Σy⁴] fold
            pltpu.VMEM((2, bs, block_p, m), X.dtype),  # the double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        kernel = functools.partial(
            _smbgd_step_bank_kernel, nonlin=nonlinearity, n_tiles=n_tiles,
            with_health=health, with_moments=moments, blowup=blowup,
        )
        x_spec = pl.BlockSpec((bs, block_p, m), lambda s, i: (s, i, 0))
        scratch = [
            pltpu.VMEM((bs, n, n), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.int32),
            pltpu.VMEM((bs, MOMENT_LEAVES), jnp.float32),
        ]
    return pl.pallas_call(
        kernel,
        grid=(n_sblocks, n_tiles),
        in_specs=[x_spec] + common_specs,
        out_specs=[
            pl.BlockSpec((bs, block_p, n), lambda s, i: (s, i, 0)),
            pl.BlockSpec((bs, n, m), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((bs, n, n), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
            pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
            pl.BlockSpec((bs, 1), lambda s, i: (s, 0)),
            pl.BlockSpec((bs, MOMENT_LEAVES), lambda s, i: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, P, n), X.dtype),
            jax.ShapeDtypeStruct((S, n, m), B.dtype),
            jax.ShapeDtypeStruct((S, n, n), H_hat.dtype),
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
            jax.ShapeDtypeStruct((S, MOMENT_LEAVES), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **_compiler_params(prefetch),
    )(X, W, B, H_hat, step, gamma_hat, active, conv)
