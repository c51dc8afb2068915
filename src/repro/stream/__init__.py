"""Multi-stream separation: banks of independent EASI/SMBGD sessions stepped
as one array program.

The paper's SMBGD freezes ``B`` inside a mini-batch so the datapath has no
loop-carried dependency; this package exploits the same property across
*sessions*: S independent separators are carried as one batched state
(leading stream axis) and stepped by one fused program — ``vmap``-native math
on CPU/GPU/TPU, a batched ``(streams, P-tiles)`` Pallas kernel on the fused
path, and ``shard_map`` over the stream axis to scale banks across devices.

Public API:
  * ``Separator``       — single-stream front-end; ``algorithm`` knob collapses
                          the three historical epoch drivers
                          (``sgd | smbgd_sequential | smbgd_batched``).
  * ``SeparatorBank``   — S-stream bank; same algorithms, batched state.
                          ``fused=True`` runs the whole-step Pallas megakernel
                          on persistent padded state (``bank.layout``);
                          ``hyperparams=BankHyperparams(...)`` makes the bank
                          heterogeneous (per-stream μ, β, γ in one launch).
  * ``BankState``       — ``B (S, n, m)``, ``H_hat (S, n, n)``, ``step (S,)``
                          (padded shapes on the fused path).
  * ``BankHyperparams`` — per-stream ``(S,)`` SMBGD hyper-parameter arrays.
  * ``make_sharded_bank_step`` / ``bank_sharding`` — stream-axis device
    parallelism (streams are independent: no collectives in the hot path).

Pallas kernels run through the interpreter on the CPU backend and compile to
Mosaic on a TPU (``repro.kernels.interpret_mode``).
"""
from repro.core.smbgd import BankHyperparams
from repro.stream.bank import BankState, SeparatorBank
from repro.stream.separator import ALGORITHMS, Separator
from repro.stream.sharding import bank_sharding, make_sharded_bank_step

__all__ = [
    "ALGORITHMS",
    "BankHyperparams",
    "BankState",
    "Separator",
    "SeparatorBank",
    "bank_sharding",
    "make_sharded_bank_step",
]
