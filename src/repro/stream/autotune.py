"""Persisted 2-D autotune cache for the bank megakernel.

``benchmarks/stream_throughput.py --autotune`` sweeps the megakernel's
``(block_p, block_s)`` tile geometry (also toggling ``prefetch`` and the
``dtype_policy``) and persists the winning config here, keyed by the problem
shape and backend:

    "S=64,P=32,m=4,n=2,backend=cpu-interpret": {
        "block_p": 32, "block_s": 64, "prefetch": false,
        "fused_tick_s": ...,            # measured, f32 policy
        "bf16_fused_tick_s": ...,       # same geometry, bf16 storage
        "persistent_bytes_per_session": 1032,
        "bf16_persistent_bytes_per_session": 520,
        "tuned_at": "2026-08-07T..."
    }

``SeparatorBank`` consults the cache by default (``autotune=True``) for any
GEOMETRY knob left unset — ``block_p``, ``block_s``, ``prefetch`` — so a
tuned deployment gets the swept tiling without threading numbers by hand.
``dtype_policy`` is recorded but NEVER auto-applied: storage precision
changes results (within tested tolerance, but still), so it stays an
explicit caller decision.

The cache file defaults to ``AUTOTUNE.json`` at the repo root (checked in;
CI's ``--autotune-smoke`` gate keeps it fresh) and can be pointed elsewhere
with ``REPRO_AUTOTUNE_CACHE``.  All lookups are best-effort: a missing or
corrupt cache silently falls back to the derived defaults — tuning is a perf
knob, never a correctness dependency.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Dict, Optional

import jax

from repro.kernels import interpret_mode

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_PATH = Path(__file__).resolve().parents[3] / "AUTOTUNE.json"

# knobs SeparatorBank may adopt from a cache hit (never dtype_policy)
GEOMETRY_KEYS = ("block_p", "block_s", "prefetch")

# (path, mtime) -> parsed cache; re-read only when the file changes
_memo: Dict[tuple, dict] = {}


def cache_path(path: Optional[str] = None) -> Path:
    if path is not None:
        return Path(path)
    env = os.environ.get(CACHE_ENV)
    return Path(env) if env else _DEFAULT_PATH


def backend_tag(interpret: Optional[bool] = None) -> str:
    """Backend half of the cache key: tuned numbers never steer a different
    lowering (interpret-mode timings are meaningless on real TPU)."""
    if interpret is None:
        interpret = interpret_mode()
    return f"{jax.default_backend()}{'-interpret' if interpret else ''}"


def cache_key(
    S: int, P: int, m: int, n: int, backend: Optional[str] = None
) -> str:
    if backend is None:
        backend = backend_tag()
    return f"S={S},P={P},m={m},n={n},backend={backend}"


def load_cache(path: Optional[str] = None) -> dict:
    """Parsed cache file (``{}`` when absent/corrupt), memoized on mtime."""
    p = cache_path(path)
    try:
        mtime = p.stat().st_mtime_ns
    except OSError:
        return {}
    memo_key = (str(p), mtime)
    got = _memo.get(memo_key)
    if got is None:
        try:
            got = json.loads(p.read_text())
            if not isinstance(got, dict):
                got = {}
        except (OSError, ValueError):
            got = {}
        _memo.clear()  # one live entry per path is plenty
        _memo[memo_key] = got
    return got


def lookup(
    S: int,
    P: int,
    m: int,
    n: int,
    *,
    interpret: Optional[bool] = None,
    path: Optional[str] = None,
) -> Optional[dict]:
    """The cached entry for this shape on this backend, or None."""
    entry = load_cache(path).get(cache_key(S, P, m, n, backend_tag(interpret)))
    return entry if isinstance(entry, dict) else None


def store(
    S: int,
    P: int,
    m: int,
    n: int,
    entry: dict,
    *,
    interpret: Optional[bool] = None,
    path: Optional[str] = None,
) -> Path:
    """Write/overwrite one key's entry (read-modify-write of the JSON file)."""
    p = cache_path(path)
    cache = dict(load_cache(path))
    cache[cache_key(S, P, m, n, backend_tag(interpret))] = entry
    p.write_text(json.dumps(cache, indent=2, sort_keys=True) + "\n")
    _memo.clear()
    return p
