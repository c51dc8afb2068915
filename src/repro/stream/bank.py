"""SeparatorBank: S independent separator sessions as one batched program.

State carries a leading stream axis — ``B (S, n, m)``, ``H_hat (S, n, n)``,
``step (S,)`` — and every step is one fused array program:

  * non-Pallas paths are the single-stream step functions ``jax.vmap``-ed over
    the stream axis (op-for-op the same math, so a bank of S streams matches S
    independent runs to float tolerance),
  * the Pallas path routes the weighted gradient sum of ALL streams through
    one ``(streams, P-tiles)`` grid launch of the fused EASI-gradient kernel
    (``kernels.easi_gradient.ops.easi_gradient_bank``) — S kernel dispatches
    collapse into one,
  * ``fused=True`` goes further: the WHOLE step (``Y = X Bᵀ``, nonlinearity,
    weighted gradient sum, SMBGD commit) is one ``(streams, P-tiles)``
    megakernel launch (``ops.smbgd_step_bank``) on **persistent padded
    state**: ``init`` establishes a lane-aligned layout once (``bank.layout``)
    and every tick runs at padded shapes — pad/unpad happen only at the API
    boundary (admission, eviction, diagnostics, ``unpad_state``/``unpad_y``).
    Pair with ``make_step(donate=True)`` and steady-state serving allocates
    nothing: state buffers are donated back to the kernel's outputs and a
    block-aligned ``X`` (see ``pad_batch``/``SeparationService``) skips every
    staging copy.

Heterogeneous banks: ``hyperparams=BankHyperparams(mu, beta, gamma)`` carries
per-stream ``(S,)`` step sizes/decays/momenta (the arXiv:1710.05384 sweep) —
the fused path feeds them to the megakernel as per-stream weight rows; the
non-fused path falls back to an equivalent vmap program.

Per-stream ``step`` counters make the bank admission-friendly: a freshly
admitted stream has ``step == 0`` and its first mini-batch gates γ off (the
paper's first-batch rule) regardless of what the other streams are doing.
``step(..., active=mask)`` freezes masked-out slots entirely — the
continuous-batching hook used by ``serve.engine.SeparationService``; the
megakernel applies the mask in-register at commit time.

Convergence statistics: every step path also produces ``BankState.conv`` —
the per-stream relative update magnitude ``‖ΔB‖_F/‖B‖_F`` of the committed
tick (identical formula in the megakernel, the PR-1 Pallas path, the vmap
path and the hetero-vmap fallback, matching the ref oracle).  The fused path
computes it in-register from the commit's own ``Ĥ′B`` product, so the serving
layer's eviction policy (``serve.ConvergencePolicy``) reads an (S,)-float
side channel per tick instead of pulling ``B``/``Ĥ`` back to the host.
``probe``/``make_probe`` expose the statistic WITHOUT the commit — the
no-mutation probe mode the serving layer's batched drift watchdog runs over
transient banks of parked (frozen) separators (``stack_states`` +
``unstack_states`` are the in/out ramps).

Memory system (PR 6): ``dtype_policy="bf16"`` stores the persistent
``B``/``Ĥ`` in bf16 — the kernels (and the vmap fallbacks) still run the
gradient fold and the commit accumulation in f32, casting only at the
load/commit boundaries, so separation quality tracks the f32 oracle within
a tested tolerance while the per-session HBM footprint halves (the
capacity number: ``bank.layout.persistent_bytes_per_session``).
``block_p``/``block_s`` load from the persisted autotune cache
(``stream.autotune``, ``AUTOTUNE.json``) when left unset;
``autotune=False`` opts out.  ``dtype_policy`` is never
auto-applied from the cache (precision is a caller decision).

Checkpointing: ``BankState`` is a plain pytree of arrays (padded or not), so
``checkpoint.Checkpointer`` round-trips it unmodified — bf16 banks
checkpoint and restore at the storage dtype (tested).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as metrics_lib
from repro.core import smbgd as smbgd_lib
from repro.core.easi import EASIConfig
from repro.core.smbgd import BankHyperparams, SMBGDConfig, SMBGDState
from repro.stream.separator import Separator


class BankState(NamedTuple):
    """Batched carry for S separator sessions (leading stream axis).

    Shapes are logical — ``B (S, n, m)``, ``H_hat (S, n, n)`` — for the vmap
    paths, or persistent-padded — ``B (S, n_pad, m_pad)``, ``H_hat (S, n_pad,
    n_pad)`` per ``SeparatorBank.layout`` — for the fused megakernel path.

    ``conv`` is the per-stream convergence statistic of the last committed
    tick — the relative update magnitude ``‖ΔB‖_F/‖B‖_F`` (see
    ``core.metrics.update_magnitude``), +inf for never-stepped streams.  It is
    produced *inside* every step path (the megakernel folds it in-register at
    commit time — no extra HBM round-trip), frozen with the rest of the slot
    under the active mask, and checkpoints/shards like any other leaf.
    ``conv=None`` (the default, for states built by legacy callers) is
    normalized to +inf on the first step.

    ``health`` is the per-stream fault bitmask of the last tick (see
    ``kernels.easi_gradient.ops.HEALTH_*``): 0 = the commit landed (or the
    slot was frozen), any set bit = the commit was REFUSED because the update
    went non-finite or blew past the static bound — the slot kept its
    pre-tick state and the serving layer decides rollback/quarantine.  It is
    a fresh per-tick verdict, not a carried statistic; ``health=None``
    (legacy states) normalizes to all-healthy zeros.

    ``moments`` is the per-stream raw [Σy², Σy⁴] fold of the last tick's Y
    (the in-kernel kurtosis telemetry; see
    ``kernels.easi_gradient.ops.MOMENT_TICK_BYTES_PER_STREAM``): zeros when
    the bank's ``moments`` flag is off, for frozen slots, and for legacy
    states (``moments=None`` normalizes like ``health``).  Like ``health``
    it is a fresh per-tick observation — the serving layer's
    ``MomentController`` turns it into an EMA kurtosis estimate and an
    adaptive μ scale; nothing in the bank ever reads it back.
    """

    B: jnp.ndarray  # (S, n, m) or (S, n_pad, m_pad)
    H_hat: jnp.ndarray  # (S, n, n) or (S, n_pad, n_pad)
    step: jnp.ndarray  # (S,) int32 — per-stream mini-batch counter
    conv: Optional[jnp.ndarray] = None  # (S,) f32 — last-tick ‖ΔB‖_F/‖B‖_F
    health: Optional[jnp.ndarray] = None  # (S,) int32 — last-tick fault bits
    moments: Optional[jnp.ndarray] = None  # (S, 2) f32 — last-tick [Σy², Σy⁴]


# -- fused row-op programs --------------------------------------------------
# Slot admission/compaction/resize each touch all six state leaves.  Run
# eagerly that is ~50 op dispatches per call (≈10 ms of pure host overhead) —
# the dominant cost of an elastic resize tick, which may activate several
# sessions at once.  Fused under jit each becomes ONE cached program.  They
# are module-level (not per-bank closures) so the jit cache keys on leaf
# shapes alone and every bank instance of the same geometry — including the
# fresh instance a resize creates via ``with_streams`` — shares the programs
# a ``prewarm`` already compiled.


@jax.jit
def _row_write_jit(B, H, step, conv, health, moments, slot, subB, subH, substep):
    """Write one logical sub-state into row ``slot``; conv/health/moments
    restart (+inf / 0 / 0).  On padded leaves the whole row is cleared and
    the logical block corner-written, so no stale junk survives."""
    if B.shape[1:] != subB.shape:  # persistent-padded bank
        rowB = (
            jnp.zeros(B.shape[1:], B.dtype)
            .at[: subB.shape[0], : subB.shape[1]]
            .set(subB.astype(B.dtype))
        )
        rowH = (
            jnp.zeros(H.shape[1:], H.dtype)
            .at[: subH.shape[0], : subH.shape[1]]
            .set(subH.astype(H.dtype))
        )
    else:
        rowB = subB.astype(B.dtype)
        rowH = subH.astype(H.dtype)
    return (
        B.at[slot].set(rowB),
        H.at[slot].set(rowH),
        step.at[slot].set(substep),
        conv.at[slot].set(jnp.inf),
        health.at[slot].set(0),
        moments.at[slot].set(0.0),
    )


@functools.partial(jax.jit, static_argnums=0)  # frozen config → hashable
def _init_state_jit(cfg: EASIConfig, key: jax.Array) -> SMBGDState:
    """Fresh-session init as one cached program (same RNG stream as the
    eager call — jit never changes values, only dispatch cost)."""
    return smbgd_lib.init_state(cfg, key)


@jax.jit
def _row_move_jit(B, H, step, conv, health, moments, dst, src):
    """Copy row ``src`` over row ``dst`` on every leaf, verbatim."""
    return (
        B.at[dst].set(B[src]),
        H.at[dst].set(H[src]),
        step.at[dst].set(step[src]),
        conv.at[dst].set(conv[src]),
        health.at[dst].set(health[src]),
        moments.at[dst].set(moments[src]),
    )


@functools.partial(jax.jit, static_argnums=0)
def _resize_rows_jit(new_S, B, H, step, conv, health, moments):
    """Prefix copy/truncate every leaf to ``new_S`` rows (grow appends blank
    slots: zero B/Ĥ, step 0, conv +inf, clean health, zero moments)."""
    old_S = B.shape[0]
    if old_S > new_S:
        return (
            B[:new_S], H[:new_S], step[:new_S],
            conv[:new_S], health[:new_S], moments[:new_S],
        )
    k = new_S - old_S
    return (
        jnp.concatenate([B, jnp.zeros((k,) + B.shape[1:], B.dtype)]),
        jnp.concatenate([H, jnp.zeros((k,) + H.shape[1:], H.dtype)]),
        jnp.concatenate([step, jnp.zeros((k,), step.dtype)]),
        jnp.concatenate([conv, jnp.full((k,), jnp.inf, jnp.float32)]),
        jnp.concatenate([health, jnp.zeros((k,), jnp.int32)]),
        jnp.concatenate([moments, jnp.zeros((k, 2), jnp.float32)]),
    )


@dataclasses.dataclass(frozen=True, eq=False)
class SeparatorBank:
    """S-stream separation engine; same ``algorithm`` knob as ``Separator``.

    ``fused=True`` selects the whole-step megakernel on persistent padded
    state (requires ``algorithm="smbgd_batched"``); ``block_p`` overrides the
    kernel's P-tile size (autotune knob; default picks ``min(512, P)``
    rounded to the sublane) and ``block_s`` the number of streams batched per
    grid cell (must divide ``n_streams``; default: the largest block Mosaic
    can tile — ``n_streams`` or a multiple of 8 — whose per-cell VMEM
    residency fits the budget; see ``ops.default_block_s``).

    ``dtype_policy`` ("f32"/"bf16") sets the persistent storage dtype of
    ``B``/``Ĥ`` (accumulation stays f32 everywhere); the default ``None``
    follows ``easi.dtype`` — the legacy contract where a bf16 config stores
    bf16 state.
    Geometry knobs left as ``None`` resolve from the persisted autotune cache
    (``AUTOTUNE.json``) unless ``autotune=False``.

    ``health_checks`` (default on) folds the per-stream health word into
    every step path (``BankState.health``) and REFUSES unhealthy commits —
    the fault-containment layer; ``blowup`` overrides the static blow-up
    bound on ``‖ΔB‖_F/‖B‖_F`` (default
    ``kernels.easi_gradient.ops.HEALTH_BLOWUP_BOUND``).

    ``moments`` (default OFF — the telemetry is opt-in, and off keeps every
    other output bit-identical to the pre-moment bank) folds the per-stream
    raw [Σy², Σy⁴] into every step/probe path (``BankState.moments``): the
    in-kernel kurtosis telemetry the serving layer's ``MomentController``
    scales μ from.  Costs 8 bytes/stream/tick of HBM (the output leaf —
    both sums fold from registers the gradient pass already holds).
    """

    easi: EASIConfig
    opt: SMBGDConfig
    n_streams: int
    algorithm: str = "smbgd_batched"
    use_pallas: bool = False
    fused: bool = False
    hyperparams: Optional[BankHyperparams] = None
    block_p: Optional[int] = None
    block_s: Optional[int] = None
    dtype_policy: Optional[str] = None  # None → follow easi.dtype
    autotune: bool = True
    health_checks: bool = True
    blowup: Optional[float] = None  # None → ops.HEALTH_BLOWUP_BOUND
    moments: bool = False  # per-stream [Σy², Σy⁴] telemetry (adaptive μ)

    def __post_init__(self) -> None:
        if self.n_streams < 1:
            raise ValueError("n_streams must be >= 1")
        from repro.kernels.easi_gradient import ops as easi_ops

        if (
            self.dtype_policy is not None
            and self.dtype_policy not in easi_ops.STORAGE_DTYPES
        ):
            raise ValueError(
                f"dtype_policy must be one of "
                f"{sorted(easi_ops.STORAGE_DTYPES)}, got {self.dtype_policy!r}"
            )
        # snapshot the caller's EXPLICIT geometry before autotune fills the
        # blanks — with_streams() re-resolves at the new width key but must
        # keep hand-set knobs winning over whatever the cache says there
        object.__setattr__(
            self,
            "_explicit_geometry",
            {
                "block_p": self.block_p,
                "block_s": self.block_s,
            },
        )
        self._resolve_autotune()
        # reuse Separator's alias resolution + validation
        sep = Separator(self.easi, self.opt, self.algorithm, self.use_pallas)
        object.__setattr__(self, "algorithm", sep.algorithm)
        if self.fused and self.algorithm != "smbgd_batched":
            raise ValueError(
                f"fused=True requires algorithm='smbgd_batched', "
                f"got {self.algorithm!r}"
            )
        if self.hyperparams is not None:
            if self.algorithm != "smbgd_batched":
                raise ValueError(
                    "per-stream hyperparams require algorithm='smbgd_batched'"
                )
            for name, v in self.hyperparams._asdict().items():
                shape = jnp.shape(v)
                if shape != (self.n_streams,):
                    raise ValueError(
                        f"hyperparams.{name} must have shape "
                        f"({self.n_streams},), got {shape}"
                    )

    def _resolve_autotune(self) -> None:
        """Fill unset GEOMETRY knobs (block_p/block_s) from the
        persisted autotune cache — best-effort, fused banks only.  The
        resolved values become the dataclass fields, so everything derived
        from this bank (sharded local banks, probe banks built by the
        serving layer) inherits the tuned geometry rather than re-resolving
        against a different shape key."""
        if not (self.fused and self.autotune):
            return
        if self.block_p is not None and self.block_s is not None:
            return
        try:
            from repro.stream import autotune as autotune_lib

            entry = autotune_lib.lookup(
                self.n_streams,
                self.opt.batch_size,
                self.easi.n_features,
                self.easi.n_components,
            )
        except Exception:  # corrupt cache must never break bank construction
            entry = None
        if not entry:
            return
        if self.block_p is None and entry.get("block_p"):
            object.__setattr__(self, "block_p", int(entry["block_p"]))
        if (
            self.block_s is None
            and entry.get("block_s")
            and self.n_streams % int(entry["block_s"]) == 0
        ):
            object.__setattr__(self, "block_s", int(entry["block_s"]))

    @property
    def resolved_dtype_policy(self) -> str:
        """``dtype_policy`` with the ``None`` default resolved against
        ``easi.dtype`` (a bf16 config stores bf16 — the legacy contract)."""
        if self.dtype_policy is not None:
            return self.dtype_policy
        from repro.kernels.easi_gradient import ops as easi_ops

        for name, dt in easi_ops.STORAGE_DTYPES.items():
            if jnp.dtype(dt) == jnp.dtype(self.easi.dtype):
                return name
        return "f32"

    @property
    def storage_dtype(self):
        """Persistent B/Ĥ dtype per ``dtype_policy`` (compute stays f32)."""
        from repro.kernels.easi_gradient import ops as easi_ops

        return easi_ops.STORAGE_DTYPES[self.resolved_dtype_policy]

    @property
    def resolved_blowup(self) -> float:
        """The static blow-up bound with the ``None`` default resolved."""
        if self.blowup is not None:
            return float(self.blowup)
        from repro.kernels.easi_gradient import ops as easi_ops

        return float(easi_ops.HEALTH_BLOWUP_BOUND)

    @functools.cached_property
    def resolved_block_s(self) -> Optional[int]:
        """Streams per grid cell of the fused step's megakernel: ``block_s``
        as set or tuned, else what ``ops.default_block_s`` resolves for this
        width and layout; None on an unfused bank."""
        if not self.fused:
            return None
        if self.block_s is not None:
            return int(self.block_s)
        from repro.kernels.easi_gradient import ops as easi_ops

        return easi_ops.default_block_s(self.n_streams, self.layout)

    @property
    def _sep(self) -> Separator:
        return Separator(self.easi, self.opt, self.algorithm, self.use_pallas)

    # -- persistent padded layout ------------------------------------------
    @property
    def layout(self):
        """Lane-aligned persistent layout (``kernels.easi_gradient.ops
        .BankLayout``) for this bank's (n, m, P) — the fused path's contract."""
        from repro.kernels.easi_gradient import ops as easi_ops

        return easi_ops.bank_layout(
            self.easi.n_components,
            self.easi.n_features,
            self.opt.batch_size,
            block_p=self.block_p,
            dtype_policy=self.resolved_dtype_policy,
        )

    def pad_state(self, state: BankState) -> BankState:
        """Logical → persistent-padded state in the STORAGE dtype (no-op if
        already padded and stored right) — the cast-in ramp of the dtype
        policy: logical f32 states (admission, stacked probe banks,
        checkpoints written before a policy change) enter bf16 banks here."""
        lay = self.layout
        dt = lay.storage_dtype
        if state.B.shape[-2:] == (lay.n_pad, lay.m_pad):
            if state.B.dtype == dt and state.H_hat.dtype == dt:
                return state
            return state._replace(
                B=state.B.astype(dt), H_hat=state.H_hat.astype(dt)
            )
        S = state.B.shape[0]
        B = (
            jnp.zeros((S, lay.n_pad, lay.m_pad), dt)
            .at[:, : lay.n, : lay.m]
            .set(state.B.astype(dt))
        )
        H = (
            jnp.zeros((S, lay.n_pad, lay.n_pad), dt)
            .at[:, : lay.n, : lay.n]
            .set(state.H_hat.astype(dt))
        )
        return BankState(
            B=B, H_hat=H, step=state.step, conv=state.conv,
            health=state.health, moments=state.moments,
        )

    def unpad_state(self, state: BankState) -> BankState:
        """Persistent-padded → logical state (no-op if already logical).
        ``moments`` carries through unchanged — the (S, 2) leaf is layout-
        independent (padded Y entries are zero, so padded and logical folds
        agree exactly)."""
        lay = self.layout
        if state.B.shape[-2:] == (lay.n, lay.m):
            return state
        return BankState(
            B=state.B[:, : lay.n, : lay.m],
            H_hat=state.H_hat[:, : lay.n, : lay.n],
            step=state.step,
            conv=state.conv,
            health=state.health,
            moments=state.moments,
        )

    def pad_batch(self, X: jnp.ndarray) -> jnp.ndarray:
        """``X (S, P, m)`` → ``(S, P_pad, m_pad)`` (no-op if already padded).
        Serving callers that stage into a padded buffer directly (see
        ``SeparationService``) skip this copy entirely."""
        lay = self.layout
        if X.shape[-2:] == (lay.P_pad, lay.m_pad):
            return X
        S = X.shape[0]
        return (
            jnp.zeros((S, lay.P_pad, lay.m_pad), X.dtype)
            .at[:, : lay.P, : lay.m]
            .set(X)
        )

    def unpad_y(self, Y: jnp.ndarray) -> jnp.ndarray:
        """Fused-path outputs ``Y (S, P_pad, n_pad)`` → logical ``(S, P, n)``."""
        lay = self.layout
        if Y.shape[-2:] == (lay.P, lay.n):
            return Y
        return Y[:, : lay.P, : lay.n]

    # -- state ------------------------------------------------------------
    def init(self, key: jax.Array) -> BankState:
        """Independent per-stream inits from ``jax.random.split(key, S)`` —
        stream s's state equals ``Separator.init(split_keys[s])`` exactly.
        Fused banks return the state already in the persistent padded layout.
        """
        keys = jax.random.split(key, self.n_streams)
        sub = jax.vmap(lambda k: smbgd_lib.init_state(self.easi, k))(keys)
        dt = self.storage_dtype
        state = BankState(
            B=sub.B.astype(dt),
            H_hat=sub.H_hat.astype(dt),
            step=sub.step,
            conv=jnp.full((self.n_streams,), jnp.inf, jnp.float32),
            health=jnp.zeros((self.n_streams,), jnp.int32),
            moments=jnp.zeros((self.n_streams, 2), jnp.float32),
        )
        return self.pad_state(state) if self.fused else state

    @staticmethod
    def _dyn(slot) -> jnp.ndarray:
        """Slot index as a traced int32 scalar.  A Python-int index is baked
        into the eager op as a constant, so every distinct slot pays its own
        one-off XLA compile — ruinous on the serving layer's backfill and
        compaction paths, which visit arbitrary slots.  As an array operand,
        one compiled program covers all indices (results are bit-identical
        either way)."""
        return jnp.asarray(slot, jnp.int32)

    def slot_outputs(self, Y: jnp.ndarray, slots) -> Tuple[np.ndarray, ...]:
        """The logical ``(P, n)`` outputs of one step for ``slots``, in
        order: host views of one fresh ``(S, P, n)`` array, bit for bit
        ``np.asarray(Y)[slot, :P, :n]``.

        ``Y`` (padded or not, sharded or not) is read to the host once,
        whole: ``S·P_pad·n_pad`` elements, whichever slots are served (1 MB
        at 256 paper-shape slots, 4 MB at 512 slots of ``n = 256``).  The
        read waits for the step, so it syncs the tick.  The logical block is
        then copied out, so a caller keeping a view pins ``S·P·n`` elements,
        never the padded rows, and no later tick writes over it."""
        P, n = self.opt.batch_size, self.easi.n_components
        host = np.array(np.asarray(Y)[:, :P, :n])
        return tuple(host[s] for s in slots)

    def init_slot(self, state: BankState, slot, key: jax.Array) -> BankState:
        """Reset one stream slot to a fresh session (admission path).  On a
        padded bank the whole padded slot is cleared, so no stale accumulator
        junk from the previous occupant survives (``init_state``'s ``Ĥ`` is
        zero, so the shared row-write program's corner-write IS the clear)."""
        sub = _init_state_jit(self.easi, key)
        return self._write_row(state, slot, sub)

    def slot_state(self, state: BankState, slot: int) -> SMBGDState:
        """Extract one stream's state as a single-stream ``SMBGDState``
        (always logical shapes — unpads the eviction boundary).  Logical
        states are the bank-independent interchange format, so bf16 storage
        casts back to the config compute dtype here."""
        state = self.unpad_state(state)  # no-op on logical state
        slot = self._dyn(slot)
        dt = self.easi.dtype
        return SMBGDState(
            B=state.B[slot].astype(dt),
            H_hat=state.H_hat[slot].astype(dt),
            step=state.step[slot],
        )

    def set_slot(self, state: BankState, slot, sub: SMBGDState) -> BankState:
        """Write a single-stream ``SMBGDState`` (logical shapes) into one
        slot — the warm-start admission path: a re-admitted session resumes
        from its frozen separator (``B``, ``Ĥ``, step counter all carried, so
        the γ step-0 gate does NOT re-apply).  ``conv`` restarts at +inf —
        the statistic describes steps taken *in this slot*."""
        return self._write_row(state, slot, sub)

    def _write_row(self, state: BankState, slot, sub: SMBGDState) -> BankState:
        """One fused-program slot write (see ``_row_write_jit``): pads the
        logical sub-state to the bank's persistent layout when needed and
        restarts the slot's conv/health/moments telemetry."""
        B, H, step, conv, health, moments = _row_write_jit(
            state.B,
            state.H_hat,
            state.step,
            self._conv_or_default(state),
            self._health_or_default(state),
            self._moments_or_default(state),
            self._dyn(slot),
            sub.B,
            sub.H_hat,
            sub.step,
        )
        return BankState(
            B=B, H_hat=H, step=step, conv=conv, health=health, moments=moments
        )

    def _is_padded(self, state: BankState) -> bool:
        n, m = self.easi.n_components, self.easi.n_features
        return state.B.shape[-2:] != (n, m)

    @staticmethod
    def _conv_or_default(state: BankState) -> jnp.ndarray:
        """``state.conv``, or the +inf "never measured" init for states built
        by legacy callers that predate the convergence statistic."""
        if state.conv is not None:
            return state.conv
        return jnp.full((state.B.shape[0],), jnp.inf, jnp.float32)

    @staticmethod
    def _health_or_default(state: BankState) -> jnp.ndarray:
        """``state.health``, or all-healthy zeros for states built by legacy
        callers that predate the health word."""
        if state.health is not None:
            return state.health
        return jnp.zeros((state.B.shape[0],), jnp.int32)

    @staticmethod
    def _moments_or_default(state: BankState) -> jnp.ndarray:
        """``state.moments``, or all-zero [Σy², Σy⁴] rows for states built by
        legacy callers that predate the moment telemetry."""
        if state.moments is not None:
            return state.moments
        return jnp.zeros((state.B.shape[0], 2), jnp.float32)

    @staticmethod
    def stack_states(states, dtype=None) -> BankState:
        """Stack S single-stream ``SMBGDState``s into a (logical) ``BankState``
        — feed through ``pad_state`` to enter a fused bank.  Single-stream
        states carry no convergence statistic, so ``conv`` restarts at +inf.
        ``dtype`` (optional) casts ``B``/``Ĥ`` on the way in — handy when the
        target bank stores bf16 and the caller wants the cast before the
        stack allocates (``pad_state`` would otherwise do it after)."""
        B = jnp.stack([jnp.asarray(s.B) for s in states])
        H = jnp.stack([jnp.asarray(s.H_hat) for s in states])
        if dtype is not None:
            B, H = B.astype(dtype), H.astype(dtype)
        return BankState(
            B=B,
            H_hat=H,
            step=jnp.stack([jnp.asarray(s.step) for s in states]),
            conv=jnp.full((len(states),), jnp.inf, jnp.float32),
            health=jnp.zeros((len(states),), jnp.int32),
            moments=jnp.zeros((len(states), 2), jnp.float32),
        )

    def unstack_states(self, state: BankState) -> list:
        """Inverse of ``stack_states``: a list of per-stream single-stream
        ``SMBGDState``s (always logical shapes AND the config compute dtype
        — unpads fused-bank state and upcasts bf16 storage)."""
        state = self.unpad_state(state)
        dt = self.easi.dtype
        return [
            SMBGDState(
                B=state.B[s].astype(dt),
                H_hat=state.H_hat[s].astype(dt),
                step=state.step[s],
            )
            for s in range(state.B.shape[0])
        ]

    # -- shadow snapshots (fault containment) ------------------------------
    def update_shadow(
        self, shadow: BankState, state: BankState, mask: jnp.ndarray
    ) -> BankState:
        """Copy-on-healthy: refresh the shadow's slots from ``state`` where
        ``mask (S,)`` is set, keep the previous snapshot elsewhere.  The
        shadow is the per-slot last-known-good state the serving layer rolls
        a faulted session back to; it always carries ``health == 0`` (only
        healthy states are ever copied in).  Both states must share a layout
        (the service keeps the shadow in the bank's persistent layout)."""
        mask = jnp.asarray(mask) != 0
        m3 = mask[:, None, None]
        return BankState(
            B=jnp.where(m3, state.B, shadow.B),
            H_hat=jnp.where(m3, state.H_hat, shadow.H_hat),
            step=jnp.where(mask, state.step, shadow.step),
            conv=jnp.where(
                mask, self._conv_or_default(state), self._conv_or_default(shadow)
            ),
            health=jnp.zeros((state.B.shape[0],), jnp.int32),
            moments=jnp.zeros((state.B.shape[0], 2), jnp.float32),
        )

    def restore_slot(
        self, state: BankState, shadow: BankState, slot
    ) -> BankState:
        """Roll ONE slot back to its shadow snapshot (B/Ĥ/step/conv), and
        clear its health word — the first-offense recovery action."""
        return BankState(
            B=state.B.at[slot].set(shadow.B[slot]),
            H_hat=state.H_hat.at[slot].set(shadow.H_hat[slot]),
            step=state.step.at[slot].set(shadow.step[slot]),
            conv=self._conv_or_default(state)
            .at[slot]
            .set(self._conv_or_default(shadow)[slot]),
            health=self._health_or_default(state).at[slot].set(0),
            moments=self._moments_or_default(state).at[slot].set(0.0),
        )

    def copy_slot(self, dst: BankState, src: BankState, slot) -> BankState:
        """Copy one slot of ``src`` into ``dst`` (same layout on both sides)
        — how the serving layer seeds a freshly (re)admitted session's
        shadow so a rollback can never resurrect the slot's previous
        occupant."""
        slot = self._dyn(slot)
        return BankState(
            B=dst.B.at[slot].set(src.B[slot]),
            H_hat=dst.H_hat.at[slot].set(src.H_hat[slot]),
            step=dst.step.at[slot].set(src.step[slot]),
            conv=self._conv_or_default(dst)
            .at[slot]
            .set(self._conv_or_default(src)[slot]),
            health=self._health_or_default(dst).at[slot].set(0),
            moments=self._moments_or_default(dst).at[slot].set(0.0),
        )

    def corrupt_slot(
        self, state: BankState, slot, mode: str = "nan", scale: float = 1e30
    ) -> BankState:
        """Fault-injection hook (chaos tests): poison ONE slot's separator —
        ``"nan"``/``"inf"`` overwrite ``B[slot, 0, 0]``, ``"scale"``
        multiplies ``B[slot]`` by ``scale`` (a blow-up next tick).  The next
        step's health word must flag the slot; nothing else is touched."""
        if mode == "nan":
            B = state.B.at[slot, 0, 0].set(jnp.nan)
        elif mode == "inf":
            B = state.B.at[slot, 0, 0].set(jnp.inf)
        elif mode == "scale":
            B = state.B.at[slot].multiply(jnp.asarray(scale, state.B.dtype))
        else:
            raise ValueError(f"unknown corruption mode {mode!r}")
        return state._replace(B=B)

    # -- elasticity --------------------------------------------------------
    def with_streams(self, new_S: int) -> "SeparatorBank":
        """A bank identical to this one at width ``new_S`` — the resize
        primitive.  Geometry knobs the CALLER set explicitly carry over
        verbatim (an explicit ``block_s`` that no longer divides the new
        width is dropped back to autotune/default resolution rather than
        erroring); knobs that were autotune-resolved at the old width
        re-resolve against the new ``(S, P, m, n, backend)`` cache key, so a
        grown bank picks up the geometry tuned FOR that width.  Per-stream
        ``hyperparams`` rows are ``(S,)``-shaped and have no canonical resize
        — rebuild them at the new width and pass through ``replace``."""
        if new_S == self.n_streams:
            return self
        if self.hyperparams is not None:
            raise ValueError(
                "cannot resize a bank with explicit per-stream hyperparams "
                f"(rows are shaped ({self.n_streams},)); rebuild them at "
                f"width {new_S} and use dataclasses.replace"
            )
        explicit = getattr(
            self,
            "_explicit_geometry",
            {"block_p": self.block_p, "block_s": self.block_s},
        )
        block_s = explicit["block_s"]
        if block_s is not None and new_S % block_s != 0:
            block_s = None
        return dataclasses.replace(
            self,
            n_streams=new_S,
            block_p=explicit["block_p"],
            block_s=block_s,
        )

    def resize_state(self, state: BankState) -> BankState:
        """Adopt a ``BankState`` of ANY width into this bank's width by
        leaf-wise prefix copy — valid because the persistent padded layout's
        trailing dims (``n_pad``/``m_pad``) depend only on (n, m, dtype
        policy), never on S or ``block_p``, so resizing never re-lays-out a
        surviving slot (the bit-identity contract).  Growing appends blank
        slots (zero B/Ĥ, step 0, conv +inf, clean health, zero moments —
        exactly what ``init_slot``/``set_slot`` overwrite at activation, and
        NO RNG is consumed here, so fresh-init key sequences match a
        fixed-width run); shrinking truncates — the caller (see
        ``serve.SeparationService.shrink``) must have compacted live slots
        below ``new_S`` first."""
        new_S = self.n_streams
        old_S = state.B.shape[0]
        state = state._replace(
            conv=self._conv_or_default(state),
            health=self._health_or_default(state),
            moments=self._moments_or_default(state),
        )
        if old_S == new_S:
            return state
        B, H, step, conv, health, moments = _resize_rows_jit(
            new_S,
            state.B,
            state.H_hat,
            state.step,
            state.conv,
            state.health,
            state.moments,
        )
        return BankState(
            B=B, H_hat=H, step=step, conv=conv, health=health, moments=moments
        )

    def move_slot(self, state: BankState, dst, src) -> BankState:
        """Move one slot's FULL row (B, Ĥ, step, conv, health, moments) to
        another index of the same state — the compaction primitive.  Unlike
        ``copy_slot`` (cross-state shadow seeding, which restarts the
        per-slot verdicts) every leaf carries over verbatim, so a compacted
        session's trajectory — including its eviction-policy view — is
        bit-identical to never having moved.  The source row is left behind
        as-is; it lands on the free list and ``init_slot``/``set_slot``
        clear it at the next activation (or a shrink truncates it)."""
        B, H, step, conv, health, moments = _row_move_jit(
            state.B,
            state.H_hat,
            state.step,
            self._conv_or_default(state),
            self._health_or_default(state),
            self._moments_or_default(state),
            self._dyn(dst),
            self._dyn(src),
        )
        return BankState(
            B=B, H_hat=H, step=step, conv=conv, health=health, moments=moments
        )

    # -- stepping ----------------------------------------------------------
    def step(
        self,
        state: BankState,
        X: jnp.ndarray,
        active: Optional[jnp.ndarray] = None,
        hyperparams: Optional[BankHyperparams] = None,
    ) -> Tuple[BankState, jnp.ndarray]:
        """One fused mini-batch update for all streams.

        ``X (S, P, m)`` → ``Y (S, P, n)``.  ``active (S,)`` bool (optional)
        freezes masked-out slots: their state is returned unchanged (their Y
        rows are still computed — garbage-in/garbage-out for free slots).

        ``hyperparams`` (optional) overrides the bank's per-stream (μ, β, γ)
        for THIS step — as ``(S,)`` array operands, not closure constants, so
        a jitted step can vary them tick to tick without retracing (the
        serving layer's drift-watchdog μ boost rides this).  Overrides route
        non-fused banks through the hetero-vmap path and require
        ``algorithm="smbgd_batched"``.

        Fused banks run on padded shapes: ``X`` may be logical (padded here)
        or already ``(S, P_pad, m_pad)`` (zero-copy), and the returned state
        and ``Y (S, P_pad, n_pad)`` stay padded — ``unpad_state``/``unpad_y``
        at the boundary.
        """
        if hyperparams is not None and self.algorithm != "smbgd_batched":
            raise ValueError(
                "per-stream hyperparams require algorithm='smbgd_batched'"
            )
        if self.fused:
            return self._step_fused(state, X, active, hyperparams)
        new_state, Y = self._step_all(state, X, hyperparams)
        S = state.B.shape[0]
        act = (
            jnp.ones((S,), jnp.int32) if active is None else jnp.asarray(active)
        ) != 0
        moments = self._vmap_moments(Y, act)
        if active is None and not self.health_checks:
            return (
                new_state._replace(
                    health=jnp.zeros((S,), jnp.int32), moments=moments
                ),
                Y,
            )
        health = (
            self._vmap_health(new_state, Y, self.resolved_blowup)
            if self.health_checks
            else jnp.zeros((S,), jnp.int32)
        )
        # unhealthy streams refuse their commit exactly like frozen ones:
        # pre-tick B/Ĥ/step/conv survive, only the health word reports why
        commit = act & (health == 0)
        c3 = commit[:, None, None]
        new_state = BankState(
            B=jnp.where(c3, new_state.B, state.B),
            H_hat=jnp.where(c3, new_state.H_hat, state.H_hat),
            step=jnp.where(commit, new_state.step, state.step),
            conv=jnp.where(commit, new_state.conv, self._conv_or_default(state)),
            health=jnp.where(act, health, 0),
            moments=moments,
        )
        return new_state, Y

    @staticmethod
    def _vmap_health(new_state: BankState, Y: jnp.ndarray, blowup: float):
        """Per-stream health word on the vmap paths — same bit layout as the
        megakernel's in-register reduction (``easi_gradient.HEALTH_*``):
        1 non-finite B′, 2 non-finite Ĥ′, 4 non-finite Y, 8 update magnitude
        above ``blowup`` (``~(δ <= bound)`` so a NaN δ counts as blow-up)."""
        rows = lambda a: jnp.all(jnp.all(jnp.isfinite(a), axis=2), axis=1)
        fin_b = rows(new_state.B)  # lanes, then sublanes: the kernel's order
        fin_h = rows(new_state.H_hat)
        fin_y = rows(Y)
        blow = ~(new_state.conv <= blowup)
        return (
            jnp.where(fin_b, 0, 1)
            + jnp.where(fin_h, 0, 2)
            + jnp.where(fin_y, 0, 4)
            + jnp.where(blow, 8, 0)
        ).astype(jnp.int32)

    def _vmap_moments(self, Y: jnp.ndarray, act: jnp.ndarray) -> jnp.ndarray:
        """Per-stream raw [Σy², Σy⁴] on the vmap paths — the same whole-block
        reduction the megakernel folds tile-by-tile (padding-exact, so the
        two agree bit-for-bit on identical Y).  Zeros when the bank's
        ``moments`` flag is off or for masked-out streams."""
        if not self.moments:
            return jnp.zeros((Y.shape[0], 2), jnp.float32)
        y2 = Y.astype(jnp.float32) ** 2
        # lanes first, then rows — the kernel's ``_reduce_rows`` order
        rows = lambda a: jnp.sum(jnp.sum(a, axis=2), axis=1)
        mom = jnp.stack([rows(y2), rows(y2 * y2)], axis=-1)
        return jnp.where(act[:, None], mom, 0.0)

    @staticmethod
    def _donate_default(donate: Optional[bool]) -> bool:
        # On accelerators donation lets the runtime alias the persistent state
        # buffers into the kernel outputs (zero steady-state allocation).  On
        # the CPU backend XLA instead inserts defensive copies for donated
        # params — measurably slower at bank sizes — so default it off there.
        if donate is None:
            return jax.default_backend() != "cpu"
        return donate

    def make_step(
        self, donate: Optional[bool] = None, with_hyperparams: bool = False
    ):
        """Jitted ``step(state, X, active) -> (state, Y)``; with donation
        (default on accelerators) the state buffers are reused for the
        outputs, so a steady-state tick allocates nothing (the serving hot
        loop).  ``with_hyperparams=True`` builds the 4-argument flavour
        ``step(state, X, active, hyperparams)`` — per-stream (μ, β, γ) as
        traced operands, the drift-watchdog's no-retrace μ-boost hook.
        Either flavour is named ``bank_step``, the name its dispatch carries
        in a profiler trace (``PjitFunction(bank_step)``)."""
        if with_hyperparams:

            def bank_step(st, X, active, hp):
                return self.step(st, X, active=active, hyperparams=hp)

        else:

            def bank_step(st, X, active):
                return self.step(st, X, active=active)

        donate = self._donate_default(donate)
        return jax.jit(bank_step, donate_argnums=(0,) if donate else ())

    def make_epoch(self, donate: Optional[bool] = None):
        """Jitted ``epoch(state, X) -> (state, Y)`` with donated state
        (default on accelerators; see ``make_step``)."""
        donate = self._donate_default(donate)
        return jax.jit(self.epoch, donate_argnums=(0,) if donate else ())

    def probe(
        self,
        state: BankState,
        X: jnp.ndarray,
        active: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """No-commit probe step: the per-stream convergence statistic a
        ``step`` on ``X (S, P, m)`` WOULD commit — ``‖Ĥ′B‖_F/‖B‖_F`` from the
        virtual ``Ĥ′ = γ̂Ĥ + S`` — without mutating anything.  Returns
        ``(conv (S,), health (S,) int32, moments (S, 2) f32)``; streams
        masked out by ``active`` carry ``state.conv`` through (+inf for
        never-measured states) and report ``health == 0`` / zero moments
        (moments are also all-zero when the bank's ``moments`` flag is
        off).  The health word judges the VIRTUAL step
        (would this data blow the separator up?), so a quarantine probe can
        tell "still diverging" from "safe to resume" without committing.

        This is the out-of-band drift probe: parked (frozen) separators are
        stacked into a transient bank (``stack_states``/``pad_state``) and
        one launch answers "has any of them drifted?" for the whole batch.
        The fused path routes through the megakernel's freeze-only variant
        (``kernels.easi_gradient.ops.smbgd_probe_bank``) — no ``Y``/state
        writes reach HBM at all.
        """
        if self.fused:
            from repro.kernels.easi_gradient import ops as easi_ops

            lay = self.layout
            state = self.pad_state(state)
            X = self.pad_batch(X)
            hp = self._bank_hyperparams()
            W = (
                jnp.zeros((self.n_streams, lay.P_pad), jnp.float32)
                .at[:, : lay.P]
                .set(hp.within_batch_weights(lay.P))
            )
            if active is None:
                active = jnp.ones((self.n_streams,), dtype=jnp.int32)
            return easi_ops.smbgd_probe_bank(
                X,
                W,
                state.B,
                state.H_hat,
                state.step,
                hp.effective_momentum(lay.P),
                active,
                self._conv_or_default(state),
                nonlinearity=self.easi.nonlinearity,
                block_p=lay.block_p,
                block_s=self.block_s,
                health=bool(self.health_checks),
                moments=bool(self.moments),
                blowup=self.resolved_blowup,
            )
        new_state, Y = self._step_all(state, X)
        act = (
            jnp.ones((state.B.shape[0],), jnp.int32)
            if active is None
            else jnp.asarray(active)
        ) != 0
        health = (
            self._vmap_health(new_state, Y, self.resolved_blowup)
            if self.health_checks
            else jnp.zeros((state.B.shape[0],), jnp.int32)
        )
        conv = jnp.where(act, new_state.conv, self._conv_or_default(state))
        return conv, jnp.where(act, health, 0), self._vmap_moments(Y, act)

    def make_probe(self):
        """Jitted ``probe(state, X, active) -> (conv (S,), health (S,),
        moments (S, 2))`` (no donation — the probe never consumes its state;
        the frozen operands stay live)."""
        return jax.jit(lambda st, X, active: self.probe(st, X, active=active))

    def _bank_hyperparams(self) -> BankHyperparams:
        if self.hyperparams is not None:
            return self.hyperparams
        return BankHyperparams.broadcast(self.opt, self.n_streams)

    def _step_fused(
        self,
        state: BankState,
        X: jnp.ndarray,
        active: Optional[jnp.ndarray],
        hyperparams: Optional[BankHyperparams] = None,
    ):
        """Whole-step megakernel tick: one (streams, P-tiles) launch computes
        Y, the weighted gradient sum AND the commit on persistent padded
        state — nothing intermediate is materialized in HBM."""
        from repro.kernels.easi_gradient import ops as easi_ops

        lay = self.layout
        state = self.pad_state(state)  # no-op on the persistent layout
        X = self.pad_batch(X)  # no-op when staged block-aligned
        hp = hyperparams if hyperparams is not None else self._bank_hyperparams()
        # weight rows at padded P: padded samples carry zero weight
        W = (
            jnp.zeros((self.n_streams, lay.P_pad), jnp.float32)
            .at[:, : lay.P]
            .set(hp.within_batch_weights(lay.P))
        )
        gamma_hat = hp.effective_momentum(lay.P)
        if active is None:
            active = jnp.ones((self.n_streams,), dtype=jnp.int32)
        Y, B_new, H_new, step_new, conv_new, health_new, mom_new = (
            easi_ops.smbgd_step_bank(
                X,
                W,
                state.B,
                state.H_hat,
                state.step,
                gamma_hat,
                active,
                self._conv_or_default(state),
                nonlinearity=self.easi.nonlinearity,
                block_p=lay.block_p,
                block_s=self.block_s,
                health=bool(self.health_checks),
                moments=bool(self.moments),
                blowup=self.resolved_blowup,
            )
        )
        return (
            BankState(
                B=B_new,
                H_hat=H_new,
                step=step_new,
                conv=conv_new,
                health=health_new,
                moments=mom_new,
            ),
            Y,
        )

    def _step_all(
        self,
        state: BankState,
        X: jnp.ndarray,
        hyperparams: Optional[BankHyperparams] = None,
    ):
        # dtype policy on the vmap paths mirrors the megakernel's boundary
        # casts: bf16-stored banks upcast to f32, run the exact f32 step, and
        # downcast only the committed B/Ĥ — accumulation never happens at
        # storage precision.
        if state.B.dtype != jnp.float32:
            dt = state.B.dtype
            f32 = state._replace(
                B=state.B.astype(jnp.float32),
                H_hat=state.H_hat.astype(jnp.float32),
            )
            new_state, Y = self._step_all(f32, X, hyperparams)
            return (
                new_state._replace(
                    B=new_state.B.astype(dt), H_hat=new_state.H_hat.astype(dt)
                ),
                Y,
            )
        if hyperparams is not None or self.hyperparams is not None:
            return self._step_hetero(state, X, hyperparams)
        if self.algorithm == "smbgd_batched" and self.use_pallas:
            return self._step_pallas(state, X)
        sep = self._sep
        sub = SMBGDState(B=state.B, H_hat=state.H_hat, step=state.step)
        new_sub, Y = jax.vmap(sep.step)(sub, X)
        return (
            BankState(
                B=new_sub.B,
                H_hat=new_sub.H_hat,
                step=new_sub.step,
                conv=metrics_lib.update_magnitude(new_sub.B, state.B),
            ),
            Y,
        )

    def _step_hetero(
        self,
        state: BankState,
        X: jnp.ndarray,
        hyperparams: Optional[BankHyperparams] = None,
    ):
        """vmap fallback for per-stream (μ, β, γ) without the megakernel —
        the reference semantics the fused path is tested against."""
        from repro.core import easi as easi_lib

        hp = hyperparams if hyperparams is not None else self._bank_hyperparams()
        P = self.opt.batch_size
        W = hp.within_batch_weights(P)  # (S, P)
        gamma_hat = hp.effective_momentum(P)  # (S,)
        g = self.easi.g

        def one(st: SMBGDState, x, w, gh):
            Y = x @ st.B.T
            S_grad = easi_lib.batched_relative_gradient(Y, w, g)
            H_hat, B_next = smbgd_lib.smbgd_commit(
                st.step, st.H_hat, S_grad, st.B, self.opt, gamma_hat=gh
            )
            return SMBGDState(B=B_next, H_hat=H_hat, step=st.step + 1), Y

        sub = SMBGDState(B=state.B, H_hat=state.H_hat, step=state.step)
        new_sub, Y = jax.vmap(one)(sub, X, W.astype(state.B.dtype), gamma_hat)
        return (
            BankState(
                B=new_sub.B,
                H_hat=new_sub.H_hat,
                step=new_sub.step,
                conv=metrics_lib.update_magnitude(new_sub.B, state.B),
            ),
            Y,
        )

    def _step_pallas(self, state: BankState, X: jnp.ndarray):
        """Closed-form SMBGD step with the gradient sum of all S streams fused
        into one (streams, P-tiles) Pallas launch (PR-1 path: Y and the
        commit remain XLA ops around the gradient kernel)."""
        from repro.kernels.easi_gradient import ops as easi_ops

        B, H_prev = state.B, state.H_hat
        Y = jnp.einsum("spm,snm->spn", X, B)  # per-stream Y = X Bᵀ
        w = self.opt.within_batch_weights(dtype=B.dtype)
        S_grad = easi_ops.easi_gradient_bank(
            Y, w, nonlinearity=self.easi.nonlinearity
        )
        H_hat, B_next = smbgd_lib.smbgd_commit(
            state.step, H_prev, S_grad, B, self.opt
        )
        return (
            BankState(
                B=B_next,
                H_hat=H_hat,
                step=state.step + 1,
                conv=metrics_lib.update_magnitude(B_next, B),
            ),
            Y,
        )

    def epoch(
        self, state: BankState, X: jnp.ndarray
    ) -> Tuple[BankState, jnp.ndarray]:
        """One pass over ``X (S, T, m)`` for every stream; returns
        ``(state, Y (S, T', n))`` with T' = K·P (SMBGD) or T (SGD).  Fused
        banks carry padded state through the scan (and return it padded) but
        Y is returned logical.

        ``conv`` semantics: the SMBGD paths scan ``step``, so the returned
        statistic is the LAST mini-batch's ``‖ΔB‖_F/‖B‖_F`` (same scale as
        the serving tick path).  The SGD path has no mini-batch structure —
        its conv is the whole-epoch aggregate ``‖B_end−B_start‖_F/‖B_start‖_F``,
        typically far larger; don't compare it against tick-tuned thresholds.
        """
        if self.algorithm == "sgd":
            if state.B.dtype != jnp.float32:  # f32 compute (see _step_all)
                dt = state.B.dtype
                f32 = state._replace(
                    B=state.B.astype(jnp.float32),
                    H_hat=state.H_hat.astype(jnp.float32),
                )
                new_state, Y = self.epoch(f32, X)
                return (
                    new_state._replace(
                        B=new_state.B.astype(dt),
                        H_hat=new_state.H_hat.astype(dt),
                    ),
                    Y,
                )
            sep = self._sep
            sub = SMBGDState(B=state.B, H_hat=state.H_hat, step=state.step)
            new_sub, Y = jax.vmap(sep.epoch)(sub, X)
            return (
                BankState(
                    new_sub.B,
                    new_sub.H_hat,
                    new_sub.step,
                    conv=metrics_lib.update_magnitude(new_sub.B, state.B),
                ),
                Y,
            )
        S, T, m = X.shape
        P = self.opt.batch_size
        K = T // P
        Xb = X[:, : K * P].reshape(S, K, P, m).transpose(1, 0, 2, 3)  # (K, S, P, m)
        if self.fused:
            state = self.pad_state(state)
        # the scan carry must be structure-stable: normalize legacy None leaves
        state = state._replace(
            conv=self._conv_or_default(state),
            health=self._health_or_default(state),
            moments=self._moments_or_default(state),
        )

        def body(st, xb):
            st, Y = self.step(st, xb)
            return st, self.unpad_y(Y) if self.fused else Y

        state, Yb = jax.lax.scan(body, state, Xb)  # Yb (K, S, P, n)
        return state, Yb.transpose(1, 0, 2, 3).reshape(S, K * P, -1)

    # -- deployment / diagnostics -----------------------------------------
    def transform(self, state: BankState, X: jnp.ndarray) -> jnp.ndarray:
        """Per-stream separation: ``X (S, ..., m)`` → ``Y (S, ..., n)``
        (bf16-stored ``B`` upcasts to the config compute dtype first)."""
        B = self.unpad_state(state).B.astype(self.easi.dtype)
        return jnp.einsum("s...m,snm->s...n", X, B)

    def performance_index(self, state: BankState, A: jnp.ndarray) -> jnp.ndarray:
        """Per-stream Amari index against mixing ``A (m, n)`` or ``(S, m, n)``."""
        B = self.unpad_state(state).B.astype(self.easi.dtype)
        if A.ndim == 2:
            A = jnp.broadcast_to(A, (self.n_streams,) + A.shape)
        gs = jax.vmap(metrics_lib.global_system)(B, A)
        return jax.vmap(metrics_lib.amari_index)(gs)
