"""Jit'd public wrappers for the EASI-gradient kernels: padding, alignment,
dtype policy and the block geometry.

Lowering follows the backend (``repro.kernels.interpret_mode``): the Pallas
interpreter on the CPU, Mosaic on a TPU.  Every entry point takes an explicit
``interpret=`` override for tests:

  * ``easi_gradient``       — single stream,   ``Y (P, n)``    → ``S (n, n)``
  * ``easi_gradient_bank``  — S streams fused, ``Y (S, P, n)`` → ``S (S, n, n)``
  * ``smbgd_step_bank``     — whole-step megakernel: one launch computes
    ``Y = X Bᵀ``, the weighted gradient sum, the SMBGD commit AND the
    per-stream convergence statistic (relative update magnitude) for all S
    streams, on persistent-padded state (``BankLayout``).
  * ``smbgd_probe_bank``    — freeze-only fast path of the megakernel: the
    same launch geometry computes ONLY the per-stream convergence statistic
    a commit WOULD produce — no ``Y``/``B'``/``Ĥ'`` writes.  The batched
    out-of-band drift probe of parked (frozen) separators.

Block-aligned inputs take the zero-copy fast path: when an array already
matches its padded geometry the ``zeros().at[].set()`` staging copy is skipped
entirely — persistent-layout callers (``stream.SeparatorBank`` in fused mode)
pay no per-step padding.

Memory-system knobs (PR 6):

  * ``prefetch=True`` on the megakernel/probe entry points swaps the X
    BlockSpec pipeline for an explicit double-buffered ``make_async_copy``
    (bit-identical on the interpret path),
  * ``bank_layout(dtype_policy="bf16")`` stores persistent ``B``/``Ĥ`` in
    bf16 (f32 accumulation inside the kernels) — ``BankLayout`` owns the
    byte accounting (``persistent_bytes_per_session``,
    ``tick_hbm_bytes_per_stream``),
  * the default ``block_s`` is derived from the layout's VMEM residency
    against the kernels' VMEM limit (``default_block_s``), not a hardcoded
    cap.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.easi_gradient.easi_gradient import (  # noqa: F401 — health
    HEALTH_BLOWUP,  # constants re-exported: ops is the public kernel surface
    HEALTH_BLOWUP_BOUND,
    HEALTH_NONFINITE_B,
    HEALTH_NONFINITE_H,
    HEALTH_NONFINITE_Y,
    HEALTH_OK,
    MOMENT_LEAVES,
    VMEM_LIMIT_BYTES,
    easi_gradient_bank_pallas,
    easi_gradient_pallas,
    smbgd_probe_bank_pallas,
    smbgd_step_bank_pallas,
)

_HEALTH_BITS = (
    (HEALTH_NONFINITE_B, "nonfinite-B"),
    (HEALTH_NONFINITE_H, "nonfinite-H"),
    (HEALTH_NONFINITE_Y, "nonfinite-Y"),
    (HEALTH_BLOWUP, "blowup"),
)


def describe_health(word: int) -> str:
    """Human-readable rendering of a per-stream health word (for eviction
    provenance and logs): ``"ok"`` or a ``+``-joined flag list."""
    flags = [name for bit, name in _HEALTH_BITS if int(word) & bit]
    return "+".join(flags) if flags else "ok"


# The ENTIRE extra HBM traffic of ``health_checks=True``: one int32 health
# word written per stream per tick.  Every other ingredient of the word (the
# isfinite folds, the blow-up bound on the conv statistic) reads values the
# kernel already holds in registers — benchmarks/stream_throughput.py --health
# gates this against the ≤5% acceptance bar using the layout's analytic tick
# bytes.
HEALTH_TICK_BYTES_PER_STREAM = 4

# The ENTIRE extra HBM traffic of ``moments=True``: one (2,) f32 row of raw
# [Σy², Σy⁴] sums written per stream per tick.  Both sums fold from the Y
# registers the gradient pass already holds (see ``_fold_moment_tile``), so —
# exactly like the health word — the telemetry's HBM cost is its output leaf
# and nothing else.  benchmarks/stream_throughput.py --adapt gates this
# against the same ≤5% bar.
MOMENT_TICK_BYTES_PER_STREAM = MOMENT_LEAVES * 4

_LANE = 128  # TPU lane width (last-dim alignment)
_SUBLANE = 8  # f32 sublane

# Persistent-state storage dtypes selectable via ``dtype_policy``.  Storage is
# what B/Ĥ occupy in HBM between ticks; the kernels ALWAYS accumulate the
# gradient fold and the commit in f32 (casts only at load/commit boundaries),
# so "bf16" halves the persistent HBM footprint per session without touching
# the accumulation precision.
STORAGE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}

# VMEM budget for the default block_s derivation: resident bytes per stream x
# block_s must fit.  Compiled megakernels run under an explicit Mosaic
# ``vmem_limit_bytes`` of 64 MiB (``easi_gradient.VMEM_LIMIT_BYTES``; TPU
# v5e's compiler otherwise scopes kernels to 16 MiB of its 128 MiB VMEM, as
# its out-of-memory report states).  ``_resident_bytes_per_stream``
# over-counts what Mosaic allocates, so the whole limit is the budget.  The
# interpreter has no VMEM; the same accounting bounds its per-cell host
# temporaries.
_VMEM_BUDGET_ENV = "REPRO_VMEM_BUDGET_BYTES"
_DEFAULT_VMEM_BUDGET = VMEM_LIMIT_BYTES
_DEFAULT_INTERPRET_BUDGET = 64 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_geometry(P: int, n: int, block_p: int | None, interpret: bool):
    n_pad = _round_up(max(n, _SUBLANE), _LANE if not interpret else _SUBLANE)
    if block_p is None:
        block_p = min(512, _round_up(P, _SUBLANE))
    P_pad = _round_up(P, block_p)
    return P_pad, n_pad, block_p


@dataclasses.dataclass(frozen=True)
class BankLayout:
    """Persistent padded layout of a separator bank's state and batches.

    Established once (at ``SeparatorBank.init``); every per-tick tensor is
    carried at these padded shapes so the steady-state serving path never
    re-pads.  Pad/unpad happen only at the API boundary (admission, eviction,
    diagnostics).  ``interpret`` relaxes lane alignment to the f32 sublane so
    CPU interpret-mode tests exercise realistic (non-trivial) padding.

    ``dtype_policy`` names the persistent storage dtype of ``B``/``Ĥ``
    (``"f32"`` or ``"bf16"``; see ``STORAGE_DTYPES``) — the layout owns the
    bank's HBM byte accounting, so capacity math (sessions per device,
    bytes per tick) reads straight off it.
    """

    n: int  # logical components
    m: int  # logical features
    P: int  # logical mini-batch
    n_pad: int
    m_pad: int
    P_pad: int
    block_p: int
    dtype_policy: str = "f32"

    @property
    def storage_dtype(self):
        """Persistent B/Ĥ storage dtype (kernels still accumulate in f32)."""
        return STORAGE_DTYPES[self.dtype_policy]

    @property
    def persistent_bytes_per_session(self) -> int:
        """HBM bytes one session's persistent state occupies between ticks:
        padded ``B`` + ``Ĥ`` at the storage dtype, plus the int32 ``step``
        and f32 ``conv`` scalars.  THE capacity number — sessions per device
        = HBM budget / this."""
        itemsize = jnp.dtype(self.storage_dtype).itemsize
        return (self.n_pad * self.m_pad + self.n_pad * self.n_pad) * itemsize + 4 + 4

    @property
    def tick_hbm_bytes_per_stream(self) -> int:
        """Estimated HBM traffic one stream contributes to one megakernel
        tick: read X + W, read AND write B/Ĥ (storage dtype), write Y, plus
        the scalar side channels.  An analytic floor — actual traffic adds
        re-reads only if the compiler spills."""
        itemsize = jnp.dtype(self.storage_dtype).itemsize
        x_bytes = self.P_pad * self.m_pad * 4
        w_bytes = self.P_pad * 4
        y_bytes = self.P_pad * self.n_pad * 4
        state_bytes = 2 * (self.n_pad * self.m_pad + self.n_pad * self.n_pad) * itemsize
        return x_bytes + w_bytes + y_bytes + state_bytes + 4 * 2 + 4 * 2

    def vmem_resident_bytes_per_stream(self) -> int:
        """Conservative per-stream VMEM residency of one megakernel grid
        cell — what the default ``block_s`` derivation budgets against."""
        return _resident_bytes_per_stream(
            self.block_p, self.n_pad, self.m_pad,
            x_itemsize=4,
            state_itemsize=jnp.dtype(self.storage_dtype).itemsize,
        )


def bank_layout(
    n: int,
    m: int,
    P: int,
    *,
    block_p: int | None = None,
    interpret: bool | None = None,
    dtype_policy: str = "f32",
) -> BankLayout:
    """Compute the lane/sublane-aligned persistent layout for ``(n, m, P)``.

    One geometry rule for the whole stack: ``n`` (last dim of Y/Ĥ) and ``m``
    (last dim of X/B) are lane-aligned; ``P`` rounds up to a whole number of
    ``block_p`` tiles.  ``dtype_policy`` selects the persistent storage dtype
    (see ``BankLayout``).
    """
    if interpret is None:
        interpret = interpret_mode()
    if dtype_policy not in STORAGE_DTYPES:
        raise ValueError(
            f"dtype_policy must be one of {sorted(STORAGE_DTYPES)}, "
            f"got {dtype_policy!r}"
        )
    P_pad, n_pad, block_p = _pad_geometry(P, n, block_p, interpret)
    m_pad = _round_up(max(m, _SUBLANE), _LANE if not interpret else _SUBLANE)
    return BankLayout(
        n=n, m=m, P=P, n_pad=n_pad, m_pad=m_pad, P_pad=P_pad, block_p=block_p,
        dtype_policy=dtype_policy,
    )


@functools.partial(jax.jit, static_argnames=("nonlinearity", "block_p", "interpret"))
def easi_gradient(
    Y: jnp.ndarray,
    w: jnp.ndarray,
    *,
    nonlinearity: str = "cubic",
    block_p: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Weighted EASI relative-gradient sum ``S (n, n)`` for ``Y (P, n)``, ``w (P,)``.

    Pads n to the 128-lane boundary and P to a sublane-aligned block; zero
    padding is exact (zero rows/cols contribute nothing; the identity term is
    computed from the real Σw and sliced back).  All nonlinearities in the bank
    satisfy g(0)=0, which the padding relies on (asserted in tests).
    """
    if interpret is None:
        interpret = interpret_mode()
    P, n = Y.shape
    P_pad, n_pad, block_p = _pad_geometry(P, n, block_p, interpret)
    if (P_pad, n_pad) == (P, n):  # block-aligned: no staging copy
        Yp = Y
        wp = w.reshape(P, 1).astype(jnp.float32)
    else:
        Yp = jnp.zeros((P_pad, n_pad), Y.dtype).at[:P, :n].set(Y)
        wp = jnp.zeros((P_pad, 1), jnp.float32).at[:P, 0].set(w.reshape(-1))
    S = easi_gradient_pallas(
        Yp, wp, nonlinearity=nonlinearity, block_p=block_p, interpret=interpret
    )
    # Padded diagonal entries carry sum(w)·I — slicing removes them.
    return S[:n, :n]


@functools.partial(jax.jit, static_argnames=("nonlinearity", "block_p", "interpret"))
def easi_gradient_bank(
    Y: jnp.ndarray,
    w: jnp.ndarray,
    *,
    nonlinearity: str = "cubic",
    block_p: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Bank form: ``Y (S, P, n)`` with shared weights ``w (P,)`` →
    ``S_out (S, n, n)`` in one fused (streams, tiles) launch.

    Same padding contract as ``easi_gradient`` — padding rows/cols are zero and
    contribute nothing (g(0)=0 for the whole bank), so each stream's slice is
    bit-identical to a single-stream launch with the same block geometry.
    """
    if interpret is None:
        interpret = interpret_mode()
    S_streams, P, n = Y.shape
    P_pad, n_pad, block_p = _pad_geometry(P, n, block_p, interpret)
    if (P_pad, n_pad) == (P, n):  # block-aligned: no per-step staging copy
        Yp = Y
        wp = w.reshape(P, 1).astype(jnp.float32)
    else:
        Yp = jnp.zeros((S_streams, P_pad, n_pad), Y.dtype).at[:, :P, :n].set(Y)
        wp = jnp.zeros((P_pad, 1), jnp.float32).at[:P, 0].set(w.reshape(-1))
    S = easi_gradient_bank_pallas(
        Yp, wp, nonlinearity=nonlinearity, block_p=block_p, interpret=interpret
    )
    return S[:, :n, :n]


def _resident_bytes_per_stream(
    block_p: int,
    n_pad: int,
    m_pad: int,
    *,
    x_itemsize: int = 4,
    state_itemsize: int = 4,
) -> int:
    """Conservative VMEM bytes ONE stream holds in a step-megakernel grid
    cell (the probe kernel holds less):

      * every pipelined block twice (Pallas double-buffers them): the X tile
        (the prefetch kernel's manual double buffer is the same two tiles),
        the W rows (a ``(block_p, 1)`` block is lane-padded to 128), the Y
        tile, ``B``/``Ĥ`` in and ``B′``/``Ĥ′`` out at the storage dtype, and
        eight ``(S, 1)``/``(S, 2)`` side-channel rows (lane-padded, 512 B
        per stream each);
      * the f32 gradient accumulator;
      * the f32 working copies of ``B`` and ``Ĥ`` the commit holds — twice
        when the storage dtype is narrower, since the kernel upcasts both
        and holds ``Ĥ′``/``ΔB``/``B′`` in f32 before the downcast;
      * three f32-sized copies of every contraction operand, for the
        HIGHEST-precision matmuls (``easi_gradient._dot``) split each f32
        operand into bf16 parts: the X tile and ``B`` (Y), Y, G and W·Y
        (the gradient fold), ``Ĥ′`` and ``B`` (the commit).

    Compiled on TPU v5e at ``m=4, n=2`` (128-lane padding) Mosaic allocated
    about 1,044 KiB per stream for f32 state and 768 KiB for bf16 at
    ``block_p=8``, and 5,308 KiB at ``block_p=512``; this count gives
    1,360, 1,232 and 5,896 KiB."""
    lane_row = _LANE * 4
    rows = 2 * block_p * (m_pad * x_itemsize + lane_row + n_pad * x_itemsize)
    side = 2 * 8 * lane_row
    logical_state = n_pad * m_pad + n_pad * n_pad
    state = 2 * 2 * logical_state * state_itemsize
    acc = n_pad * n_pad * 4
    working = logical_state * 4 * (1 if state_itemsize >= 4 else 2)
    operands = block_p * (m_pad + 3 * n_pad) + 2 * n_pad * m_pad + n_pad * n_pad
    dots = 3 * 4 * operands
    return rows + side + state + acc + working + dots


def vmem_budget_bytes(interpret: bool) -> int:
    """The per-cell VMEM budget the default ``block_s`` derivation targets.
    Override with ``REPRO_VMEM_BUDGET_BYTES`` (note: resolved at trace time —
    a jitted caller caches the resolution with the program)."""
    env = os.environ.get(_VMEM_BUDGET_ENV)
    if env:
        return int(env)
    return _DEFAULT_INTERPRET_BUDGET if interpret else _DEFAULT_VMEM_BUDGET


def _default_block_s(
    S: int, *, resident_bytes: int, interpret: bool
) -> int:
    """Largest stream-block whose residency fits the VMEM budget —
    ``resident_bytes × block_s ≤ vmem_budget_bytes()`` — among the widths
    Mosaic can tile: ``S`` itself or a divisor of ``S`` that is a multiple
    of 8 (the per-stream ``(block_s, 1)`` side-channel blocks are bound by
    the sublane rule).  Streams batched per grid cell amortize per-cell
    launch overhead; per-stream math is independent so any block is
    numerically equivalent (tested).  Deriving the cap from the layout's
    residency (instead of a hardcoded 8/32) means large ``(m, n)`` shapes
    shrink ``block_s`` instead of silently blowing VMEM.  A width with no
    such block that fits fails loudly on compiled backends; the interpreter
    has no tiling rule and no VMEM, so it falls back to any divisor (and
    clamps to 1)."""
    budget = vmem_budget_bytes(interpret)
    cap = budget // max(resident_bytes, 1)
    tiled = [
        bs for bs in range(min(S, cap), 0, -1)
        if S % bs == 0 and (bs == S or bs % _SUBLANE == 0)
    ]
    if tiled:
        return tiled[0]
    if not interpret:
        raise ValueError(
            f"every tileable stream-block of a {S}-stream bank exceeds the "
            f"VMEM budget ({budget} bytes at {resident_bytes} bytes per "
            f"stream; the block must be {S} or a multiple of {_SUBLANE} "
            f"dividing it) — use a width that is a multiple of {_SUBLANE}, "
            f"shrink block_p, or raise {_VMEM_BUDGET_ENV}"
        )
    for bs in range(min(S, max(cap, 1)), 0, -1):
        if S % bs == 0:
            return bs
    return 1


def default_block_s(
    S: int,
    layout: BankLayout,
    *,
    interpret: bool | None = None,
) -> int:
    """Public form of the default ``block_s`` derivation for a layout —
    what ``smbgd_step_bank`` resolves when ``block_s=None`` (benchmarks and
    tests use this to predict/verify the resolution)."""
    if interpret is None:
        interpret = interpret_mode()
    return _default_block_s(
        S,
        resident_bytes=layout.vmem_resident_bytes_per_stream(),
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "nonlinearity", "block_p", "block_s", "interpret", "prefetch",
        "health", "moments", "blowup",
    ),
)
def smbgd_step_bank(
    X: jnp.ndarray,
    W: jnp.ndarray,
    B: jnp.ndarray,
    H_hat: jnp.ndarray,
    step: jnp.ndarray,
    gamma_hat: jnp.ndarray,
    active: jnp.ndarray,
    conv: jnp.ndarray | None = None,
    *,
    nonlinearity: str = "cubic",
    block_p: int | None = None,
    block_s: int | None = None,
    interpret: bool | None = None,
    prefetch: bool = False,
    health: bool = True,
    moments: bool = False,
    blowup: float = HEALTH_BLOWUP_BOUND,
):
    """Whole-step fused bank tick on persistent-padded state (zero staging).

    All tensor inputs must already be in the ``bank_layout`` geometry — this
    is the steady-state serving hot path and it refuses to silently pad:

      * ``X (S, P_pad, m_pad)``, ``W (S, P_pad, 1)`` f32 weight rows
        (per-stream w_p = μ_s β_s^{P-1-p}, zero in padded rows),
      * ``B (S, n_pad, m_pad)``, ``H_hat (S, n_pad, n_pad)`` — in the
        layout's storage dtype (f32 or bf16; the kernel accumulates in f32
        either way and writes back in the storage dtype),
      * ``step (S,)`` or ``(S, 1)`` int32, ``gamma_hat (S,)`` or ``(S, 1)``
        f32 (γ̂_s = γ_s β_s^{P-1}), ``active (S,)`` or ``(S, 1)`` bool/int,
      * ``conv (S,)`` or ``(S, 1)`` f32 — previous per-stream convergence
        statistic, carried through for frozen streams (defaults to +inf,
        "never measured").

    ``block_s`` batches that many streams per grid cell (default: the
    largest divisor of S whose per-cell residency fits the VMEM budget —
    see ``default_block_s``).  ``prefetch=True`` double-buffers the X tile
    DMA (bit-identical on the interpret path).  Returns
    ``(Y (S, P_pad, n_pad), B', H_hat', step' (S,), conv' (S,),
    health' (S,), moments' (S, 2))`` where ``conv'`` is the relative update
    magnitude ``‖Ĥ′B‖_F/‖B‖_F`` computed inside the commit (see
    ``core.metrics.update_magnitude`` for the reference formula),
    ``health'`` is the int32 per-stream fault bitmask (``HEALTH_*``;
    non-finite B'/Ĥ'/Y or ``conv' > blowup``) and ``moments'`` the raw
    per-stream [Σy², Σy⁴] fold over this tick's Y (zeros when
    ``moments=False`` — purely observational, every other output is
    bit-identical either way).  ``health=True`` (default) also refuses
    unhealthy commits in-kernel — the slot keeps its pre-tick state like a
    frozen stream; ``health=False`` restores the pre-containment
    commit-on-active behaviour and returns zeros (the overhead baseline for
    ``benchmarks --health``).
    """
    if interpret is None:
        interpret = interpret_mode()
    S_streams, P_pad, m_pad = X.shape
    n_pad = B.shape[1]
    if block_p is None:
        block_p = min(512, _round_up(P_pad, _SUBLANE))
    if block_s is None:
        block_s = _default_block_s(
            S_streams,
            resident_bytes=_resident_bytes_per_stream(
                block_p, n_pad, m_pad,
                x_itemsize=X.dtype.itemsize,
                state_itemsize=B.dtype.itemsize,
            ),
            interpret=interpret,
        )
    if P_pad % block_p or n_pad % _SUBLANE or m_pad % _SUBLANE:
        raise ValueError(
            f"smbgd_step_bank requires persistent-layout inputs; got "
            f"P={P_pad} (block_p={block_p}), n={n_pad}, m={m_pad}"
        )
    if S_streams % block_s:
        raise ValueError(
            f"block_s={block_s} must divide the stream count {S_streams}"
        )
    Wp = W.reshape(S_streams, P_pad, 1).astype(jnp.float32)
    step2 = step.reshape(S_streams, 1).astype(jnp.int32)
    gamma2 = gamma_hat.reshape(S_streams, 1).astype(jnp.float32)
    active2 = active.reshape(S_streams, 1).astype(jnp.int32)
    if conv is None:
        conv = jnp.full((S_streams, 1), jnp.inf, jnp.float32)
    conv2 = conv.reshape(S_streams, 1).astype(jnp.float32)
    Y, B_new, H_new, step_new, conv_new, health_new, mom_new = (
        smbgd_step_bank_pallas(
            X,
            Wp,
            B,
            H_hat,
            step2,
            gamma2,
            active2,
            conv2,
            nonlinearity=nonlinearity,
            block_p=block_p,
            block_s=block_s,
            interpret=interpret,
            prefetch=prefetch,
            health=health,
            moments=moments,
            blowup=blowup,
        )
    )
    return (
        Y,
        B_new,
        H_new,
        step_new.reshape(S_streams),
        conv_new.reshape(S_streams),
        health_new.reshape(S_streams),
        mom_new,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "nonlinearity", "block_p", "block_s", "interpret", "prefetch",
        "health", "moments", "blowup",
    ),
)
def smbgd_probe_bank(
    X: jnp.ndarray,
    W: jnp.ndarray,
    B: jnp.ndarray,
    H_hat: jnp.ndarray,
    step: jnp.ndarray,
    gamma_hat: jnp.ndarray,
    active: jnp.ndarray,
    conv: jnp.ndarray | None = None,
    *,
    nonlinearity: str = "cubic",
    block_p: int | None = None,
    block_s: int | None = None,
    interpret: bool | None = None,
    prefetch: bool = False,
    health: bool = True,
    moments: bool = False,
    blowup: float = HEALTH_BLOWUP_BOUND,
):
    """Freeze-only probe launch: the conv statistic a ``smbgd_step_bank``
    tick WOULD commit, without committing anything.

    Same persistent-layout contract and block geometry as ``smbgd_step_bank``
    (it refuses to silently pad); returns ``(conv' (S,), health' (S,),
    moments' (S, 2))`` — the virtual per-stream relative update magnitude,
    with ``conv`` (default +inf) carried through for streams masked out by
    ``active``, the int32 health word that commit would have raised
    (all-zero when ``health=False``; quarantined sessions are probed for
    sanity through it), and the raw [Σy², Σy⁴] fold over the probe's Y
    (zeros when ``moments=False``).  The state operands are never written:
    this is the batched out-of-band drift probe of parked (frozen)
    separators, one launch per ``S``-wide probe batch.
    """
    if interpret is None:
        interpret = interpret_mode()
    S_streams, P_pad, m_pad = X.shape
    n_pad = B.shape[1]
    if block_p is None:
        block_p = min(512, _round_up(P_pad, _SUBLANE))
    if block_s is None:
        block_s = _default_block_s(
            S_streams,
            resident_bytes=_resident_bytes_per_stream(
                block_p, n_pad, m_pad,
                x_itemsize=X.dtype.itemsize,
                state_itemsize=B.dtype.itemsize,
            ),
            interpret=interpret,
        )
    if P_pad % block_p or n_pad % _SUBLANE or m_pad % _SUBLANE:
        raise ValueError(
            f"smbgd_probe_bank requires persistent-layout inputs; got "
            f"P={P_pad} (block_p={block_p}), n={n_pad}, m={m_pad}"
        )
    if S_streams % block_s:
        raise ValueError(
            f"block_s={block_s} must divide the stream count {S_streams}"
        )
    Wp = W.reshape(S_streams, P_pad, 1).astype(jnp.float32)
    step2 = step.reshape(S_streams, 1).astype(jnp.int32)
    gamma2 = gamma_hat.reshape(S_streams, 1).astype(jnp.float32)
    active2 = active.reshape(S_streams, 1).astype(jnp.int32)
    if conv is None:
        conv = jnp.full((S_streams, 1), jnp.inf, jnp.float32)
    conv2 = conv.reshape(S_streams, 1).astype(jnp.float32)
    conv_new, health_new, mom_new = smbgd_probe_bank_pallas(
        X,
        Wp,
        B,
        H_hat,
        step2,
        gamma2,
        active2,
        conv2,
        nonlinearity=nonlinearity,
        block_p=block_p,
        block_s=block_s,
        interpret=interpret,
        prefetch=prefetch,
        health=health,
        moments=moments,
        blowup=blowup,
    )
    return conv_new.reshape(S_streams), health_new.reshape(S_streams), mom_new
