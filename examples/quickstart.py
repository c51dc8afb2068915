"""Quickstart: blind source separation with EASI + SMBGD (the paper's system).

    PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's benchmark setting (m=4 observed mixtures of n=2
independent sources, fp32, cubic nonlinearity), trains the adaptive separator
with the SMBGD update rule (Eq. 1), and reports the Amari separation index and
the SGD-vs-SMBGD comparison on the same problem.

``AdaptiveICA`` is the single-stream front-end (``algorithm`` selects
``sgd | smbgd_sequential | smbgd_batched``; ``use_pallas=True`` routes the
gradient sum through the fused Pallas kernel — interpreted on the CPU
backend, compiled on a TPU).  To run many separation
sessions at once as one fused program, see ``repro.stream.SeparatorBank``
(examples/adaptive_stream.py) and ``serve.engine.SeparationService``.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.core import (
    AdaptiveICA,
    EASIConfig,
    SMBGDConfig,
    amari_index,
    global_system,
)
from repro.data import signals


def main():
    key = jax.random.PRNGKey(0)
    # The paper's problem: 2 independent sub-Gaussian sources, 4 mixtures.
    A, S, X = signals.make_problem(key, m=4, n=2, T=40_000)
    print(f"mixing matrix A (hidden from the separator):\n{A}")

    easi_cfg = EASIConfig(n_components=2, n_features=4, mu=2e-3, nonlinearity="cubic")
    smbgd_cfg = SMBGDConfig(batch_size=8, mu=2e-3, beta=0.9, gamma=0.5)

    for algo in ("sgd", "smbgd"):
        ica = AdaptiveICA(easi_cfg, smbgd_cfg, algorithm=algo)
        state = ica.init(jax.random.PRNGKey(42))
        pi0 = float(ica.performance_index(state, A))
        state, Y = ica.fit(state, X)
        pi = float(ica.performance_index(state, A))
        # deployment: separate fresh data with the frozen separator
        _, S2, X2 = signals.make_problem(jax.random.PRNGKey(1), m=4, n=2, T=1_000)
        Y2 = ica.transform(state, X2)
        print(
            f"[{algo:5s}] amari index: {pi0:.3f} -> {pi:.4f}   "
            f"(0 = perfect separation); deployed on {Y2.shape[0]} fresh samples"
        )

    # correlation of recovered vs true sources (up to permutation/sign)
    ica = AdaptiveICA(easi_cfg, smbgd_cfg)
    state = ica.init(jax.random.PRNGKey(42))
    state, _ = ica.fit(state, X)
    Y = ica.transform(state, X[-5000:])
    St = S[-5000:]
    C = jnp.corrcoef(Y.T, St.T)[:2, 2:]
    print(f"|corr(recovered, true)| (rows should each have one ~1 entry):\n{jnp.abs(C)}")


if __name__ == "__main__":
    main()
