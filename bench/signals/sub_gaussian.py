"""Sub-Gaussian sources of the paper's kinds (sine, square, sawtooth,
uniform noise, AM sine): source ``i`` of stream ``s`` is kind ``(i +
offset_s) mod len(kinds)``, with a random frequency and phase.

Parameters (``signals`` in the configuration): ``kinds``, ``freq_min``,
``freq_span`` (cycles per sample).
"""
import numpy as np

from benchlib.traffic import unit_variance

KINDS = ("sine", "square", "sawtooth", "uniform", "am_sine")


def sources(rng, N: int, n: int, T: int, params) -> np.ndarray:
    kinds = list(params["kinds"])
    for k in kinds:
        if k not in KINDS:
            raise ValueError(f"unknown source kind {k!r}")
    t = np.arange(T, dtype=np.float64)
    freq = params["freq_min"] + params["freq_span"] * rng.random((N, n, 1))
    phase = 2 * np.pi * rng.random((N, n, 1))
    offset = rng.integers(0, len(kinds), size=(N, 1))
    kind = (np.arange(n)[None, :] + offset) % len(kinds)  # (N, n)
    arg = 2 * np.pi * freq * t + phase
    noise = rng.uniform(-1.0, 1.0, size=(N, n, T))
    table = {
        "sine": np.sin(arg),
        "square": np.sign(np.sin(arg)),
        "sawtooth": 2.0 * np.mod(freq * t + phase, 1.0) - 1.0,
        "uniform": noise,
        "am_sine": np.sin(arg) * np.sin(2 * np.pi * 0.1 * freq * t),
    }
    out = np.empty((N, n, T))
    for j, name in enumerate(kinds):
        sel = kind == j
        out[sel] = table[name][sel]
    return unit_variance(out)
