"""Run one benchmark cell once on the chip it asks for.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration (``bench/configs/``) and a traffic mix
(``bench/traffic/``).  With ``--trace 0`` the result reports the cell's
end-to-end metrics; with ``--trace 1`` the window is traced and the result
reports its per-layer metrics (``bench/metrics/``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), then ``checks``,
each number compared with its limit.  Exits non-zero with no result line
when JAX finds fewer TPU chips than the cell asks for.

JAX's persistent compilation cache lives at ``$JAX_COMPILATION_CACHE_DIR``
when that is set, otherwise at ``.jax_cache`` in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    # libtpu logs under /tmp unless told otherwise; a run writes only inside
    # its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "bench"))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"FAIL: the program is not in this checkout: {e}", file=sys.stderr)
        return 3

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from benchlib import cell

    try:
        cell.run(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START,
        )
    except cell.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
