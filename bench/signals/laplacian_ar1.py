"""Super-Gaussian sources: unit-variance Laplacian innovations through a
one-pole smoother, ``s_t = a s_{t-1} + sqrt(1 - a^2) e_t``, with ``a`` per
source drawn from ``[smoothing_min, smoothing_max]`` (the ``signals``
parameters in the configuration).
"""
import numpy as np

from benchlib.traffic import unit_variance


def sources(rng, N: int, n: int, T: int, params) -> np.ndarray:
    a = rng.uniform(params["smoothing_min"], params["smoothing_max"], size=(N, n))
    e = rng.laplace(0.0, 1.0 / np.sqrt(2.0), size=(N, n, T))
    c = np.sqrt(1.0 - a * a)
    out = np.empty((N, n, T))
    prev = e[:, :, 0]
    out[:, :, 0] = prev
    for k in range(1, T):
        prev = a * prev + c * e[:, :, k]
        out[:, :, k] = prev
    return unit_variance(out)
