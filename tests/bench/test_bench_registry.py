"""A new configuration, signal model, traffic mix or per-layer metric is
one new file each plus its ``BENCHMARK.json`` entries: no file the
benchmark already has changes."""
import hashlib
import io
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import cell, registry  # noqa: E402


def _digests(root: Path):
    return {
        p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted((root / "bench").rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


# sessions that live two blocks, each replaced by a fresh one at once: the
# service admits and releases sessions inside the window
CHURN = {"name": "churn", "why": "test", "ring_blocks": 4, "warm_ticks": 3,
         "initial": 1.0, "lifetime": {"kind": "fixed", "blocks": 2},
         "arrivals": {"replace": True}}

# a new signal model: Rademacher (±1) sources
SIGNALS = (
    "import numpy as np\n"
    "def sources(rng, N, n, T, params):\n"
    "    return rng.choice([-1.0, 1.0], size=(N, n, T))\n"
)


def test_new_config_mix_and_metric_are_picked_up_by_name(tmp_path, monkeypatch):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digests(tmp_path)
    monkeypatch.setattr(cell, "probe_widths", lambda slots: [1])

    cfg = registry.config(tmp_path, "paper_m4n2")
    cfg.update(name="tiny_m3n3", m=3, n=3,
               signals={"model": "rademacher", "mixing_min_sv": 0.3})
    (tmp_path / "bench/configs/tiny_m3n3.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/signals/rademacher.py").write_text(SIGNALS)
    shutil.copy(tmp_path / "bench/limits/paper_m4n2.json",
                tmp_path / "bench/limits/tiny_m3n3.json")
    (tmp_path / "bench/traffic/churn.json").write_text(json.dumps(CHURN))
    (tmp_path / "bench/metrics/ticks_traced.py").write_text(
        "def read(run):\n    return float(run.reduced.ticks)\n"
    )
    bench = registry.benchmark(tmp_path)
    bench["configs"].append(dict(bench["configs"][0], name="tiny_m3n3",
                                 file="bench/configs/tiny_m3n3.json"))
    bench["workloads"].append({"name": "tiny_m3n3.churn", "config": "tiny_m3n3",
                               "traffic": "churn", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "ticks_traced", "unit": "ticks", "better": "higher",
        "source": "device_trace", "layer": "tick loop",
        "moves": "samples_per_s", "workloads": ["tiny_m3n3.churn"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert _digests(tmp_path).items() >= before.items()  # nothing edited

    got = cell.run(
        tmp_path, "tiny_m3n3.churn", seed=3, seconds=0.3, traced=True,
        t_start=time.perf_counter(), require_chip=False, sessions=2,
        out=io.StringIO(), err=io.StringIO(),
    )
    assert got["correct"], got["checks"]
    ticks = got["metrics"]["ticks_traced"]["value"]
    assert ticks >= 2
    assert got["failed"] == 0 and got["attempted"] > 0
    # each slot: a session served two ticks, released on the third (its
    # source drained), replaced before the fourth
    assert got["compared"]["of"] >= 2 * ((3 + ticks) // 3)
    assert got["compared"]["sessions"] == got["compared"]["of"]
    assert got["checks"]["compiles_in_window"]["value"] == 0
    # the cell's own metric only: the others list the cells they belong to
    assert set(got["metrics"]) == {"ticks_traced"}
