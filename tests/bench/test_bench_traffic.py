"""The benchmark's load generator: reproducible from its seed alone."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import registry  # noqa: E402
from benchlib.traffic import Population, RingSource, make_traffic  # noqa: E402

CONFIGS = [c["name"] for c in registry.benchmark(ROOT)["configs"]]
MIXES = sorted({w["traffic"] for w in registry.benchmark(ROOT)["workloads"]})


def _traffic(config, mix, seed, slots):
    signals = registry.signal_model(ROOT, config["signals"]["model"])
    return make_traffic(config, mix, seed, signals, slots=slots)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("config", CONFIGS)
def test_same_seed_same_traffic_other_seed_other_traffic(config, mix):
    cfg, mx = registry.config(ROOT, config), registry.traffic(ROOT, mix)
    big = 2**31 + 12345  # seeds can exceed 32 signed bits
    a = _traffic(cfg, mx, big, 4)
    b = _traffic(cfg, mx, big, 4)
    c = _traffic(cfg, mx, big + 1, 4)
    for x, y in ((a.blocks, b.blocks), (a.mixing, b.mixing), (a.B0, b.B0)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.blocks, c.blocks)
    assert a.blocks.shape == (4, mx["ring_blocks"], cfg["m"], cfg["P"])
    assert a.blocks.dtype == np.float32
    assert np.isfinite(a.blocks).all()


@pytest.mark.parametrize("config", CONFIGS)
def test_sources_have_unit_variance_and_mixing_unit_rows(config):
    cfg = registry.config(ROOT, config)
    t = _traffic(cfg, registry.traffic(ROOT, "steady"), 7, 3)
    np.testing.assert_allclose(np.linalg.norm(t.mixing, axis=-1), 1.0, rtol=1e-5)
    # mixtures of unit-variance sources through unit-norm rows
    assert 0.2 < t.blocks.std() < 2.0


def test_ring_source_serves_views_in_order_and_wraps():
    cfg = registry.config(ROOT, "paper_m4n2")
    mx = dict(registry.traffic(ROOT, "steady"), ring_blocks=3)
    t = _traffic(cfg, mx, 1, 2)
    src = RingSource(t, 1)
    got = [src.next_block(cfg["P"]) for _ in range(4)]
    assert got[0].base is not None  # a view, not a copy
    np.testing.assert_array_equal(got[3], got[0])
    np.testing.assert_array_equal(got[1], t.blocks[1, 1])
    np.testing.assert_array_equal(t.batch(np.arange(2), 4)[1], t.blocks[1, 1].T)
    assert src.pulls == 4
    with pytest.raises(ValueError):
        src.next_block(cfg["P"] + 1)


def test_ring_source_drains_after_its_lifetime():
    cfg = registry.config(ROOT, "paper_m4n2")
    t = _traffic(cfg, registry.traffic(ROOT, "steady"), 1, 3)
    src = RingSource(t, 4, lifetime=2, exhausted=EOFError)
    np.testing.assert_array_equal(src.next_block(cfg["P"]), t.blocks[1, 0])
    src.next_block(cfg["P"])
    with pytest.raises(EOFError):
        src.next_block(cfg["P"])
    assert src.pulls == 2


CHURN = {"lifetime": {"kind": "geometric", "mean_blocks": 4},
         "arrivals": {"replace": True, "per_tick": 0.5, "burst_every": 5,
                      "burst_size": 3}}


def _schedule(mix, seed, ticks=20):
    pop = Population(mix, seed, slots=8)
    got = [pop.initial()] + [pop.arrivals(k, departed=k % 3) for k in range(ticks)]
    return got, pop.lifetimes


def test_population_is_drawn_from_the_seed_and_tick_alone():
    a, life_a = _schedule(CHURN, 2**31 + 9)
    b, life_b = _schedule(CHURN, 2**31 + 9)
    c, life_c = _schedule(CHURN, 2**31 + 10)
    assert a == b and life_a == life_b
    assert life_a != life_c
    assert len(a[0]) == 8 and all(x >= 1 for x in life_a)
    ids = [i for batch in a for i in batch]
    assert ids == list(range(len(ids)))  # ids handed out in order
    # every departure replaced, bursts of 3 at ticks 5, 10, 15, and Poisson
    assert all(len(a[1 + k]) >= k % 3 for k in range(20))
    assert all(len(a[1 + k]) >= 3 + k % 3 for k in (5, 10, 15))
    assert sum(map(len, a[1:])) > sum(k % 3 for k in range(20)) + 9


def test_steady_population_admits_every_slot_once_and_forever():
    batches, lifetimes = _schedule(registry.traffic(ROOT, "steady"), 5)
    assert batches[0] == list(range(8))
    assert all(b == [] for b in batches[1:])
    assert lifetimes == [None] * 8


def test_unknown_lifetime_kind_is_refused():
    with pytest.raises(ValueError, match="lifetime"):
        Population({"lifetime": {"kind": "weibull"}}, 1, slots=2)
