"""Run one cell traced and print where its ticks go, phase by phase.

    python3 bench/phases.py --workload <name> --seed <n> --seconds <s>

Run from the root of a checkout, on the chip the cell asks for.  The cell
runs as ``bench/run.py --trace 1`` runs it, and its result line goes to
standard output as there.  Then the same trace, reduced by
``benchlib.spans``, gives one more JSON line, last on standard output:

* ``metrics``: the tick loop's per-phase metrics (``spans.METRICS``, ms
  per tick, and ``dispatches_per_tick``);
* ``phases``: per ``serve.*`` span, per tick, its self time, its whole
  time, its device-idle time (``idle_by_phase``, all in ms) and its count;
* ``dispatches``: per phase and name, per tick, most first;
* ``run_tick_cover``: the ``serve.run_tick`` spans' time over the harness's
  ``tick`` spans less their ``fetch``; ``run_tick_self``: the part of
  ``serve.run_tick`` that no child span covers;
* ``gaps``: the longest device-idle gaps, each named by its phase;
* ``span_cost_us``: one span entered and left with no profiler session
  active, measured on this host before the run.

``metrics`` and ``phases`` are null where the program has no ``serve.*``
spans.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def span_cost_us(n: int = 200_000) -> float:
    import jax

    t0 = time.perf_counter()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("serve.cost"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def summary(phases) -> dict:
    from benchlib import spans

    per = 1e3 / phases.ticks
    root = phases.total_s.get("serve.run_tick", 0.0)
    return {
        "ticks": phases.ticks,
        "metrics": spans.metrics(phases),
        "phases": {
            name: {
                "self_ms": phases.self_s[name] * per,
                "total_ms": phases.total_s[name] * per,
                "idle_ms": phases.idle_s.get(name, 0.0) * per,
                "count": phases.counts[name],
            }
            for name in sorted(phases.self_s, key=lambda k: -phases.self_s[k])
        },
        "dispatches": [
            [phase, name, n / phases.ticks]
            for (phase, name), n in sorted(
                phases.dispatch_counts.items(), key=lambda kv: -kv[1]
            )
        ],
        "run_tick_cover": root / phases.tick_s if phases.tick_s else None,
        "run_tick_self": phases.self_s.get("serve.run_tick", 0.0) / root if root else None,
        "gaps": phases.gaps,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "bench"))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from benchlib import cell, spans, trace as trace_lib

    cost = span_cost_us()
    # keep the trace the cell loads: the cell deletes its file once read
    loaded = []
    load = trace_lib.load
    trace_lib.load = lambda path: loaded.append(load(path)) or loaded[-1]
    try:
        cell.run(ROOT, args.workload, args.seed, args.seconds, True, t_start=T_START)
    except cell.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    finally:
        trace_lib.load = load
    phases = spans.reduce(loaded[0])
    line = summary(phases) if phases is not None else {"metrics": None, "phases": None}
    line["span_cost_us"] = cost
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
