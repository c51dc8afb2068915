"""Cluster serving launcher (decode cells' production path) and the LM
decode engine it drives.

``Engine`` serves batched requests with per-request lengths: chunked prefill
through ``decode_step`` semantics, greedy / temperature sampling, one decode
state per batch slot.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --local
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M

PyTree = Any


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 512
    temperature: float = 0.0  # 0 → greedy
    seed: int = 0


class Engine:
    def __init__(self, cfg: ModelConfig, params: PyTree, scfg: ServeConfig):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self._decode = jax.jit(
            lambda p, s, b: M.decode_step(p, s, b, cfg)
        )
        self.state = M.init_serve_state(cfg, scfg.max_batch, scfg.max_len)
        self.key = jax.random.PRNGKey(scfg.seed)

    def _sample(self, logits: jnp.ndarray) -> jnp.ndarray:
        logits = logits[:, -1]  # last position: (B, V), or (B, K, V) w/ codebooks
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        self.key, k = jax.random.split(self.key)
        return jax.random.categorical(k, logits / self.scfg.temperature, axis=-1)

    def prefill_and_generate(
        self, prompts: jnp.ndarray, n_new: int
    ) -> Tuple[jnp.ndarray, List[float]]:
        """prompts: (B, T_prompt[, K]); returns (B, n_new[, K]) generated
        tokens (greedy/temperature).  Prefill is token-streamed through the
        recurrent state machinery — one code path for all families."""
        B, T = prompts.shape[0], prompts.shape[1]
        assert B == self.scfg.max_batch
        state = M.init_serve_state(self.cfg, B, self.scfg.max_len)
        logits = None
        for t in range(T):  # chunked prefill (chunk = 1 keeps it family-agnostic)
            tok = prompts[:, t : t + 1]
            logits, state = self._decode(self.params, state, {"tokens": tok})
        out = []
        tok = self._sample(logits)[:, None] if not self.cfg.n_codebooks else self._sample(logits)[:, None, :]
        for _ in range(n_new):
            out.append(tok)
            logits, state = self._decode(self.params, state, {"tokens": tok})
            tok = self._sample(logits)[:, None] if not self.cfg.n_codebooks else self._sample(logits)[:, None, :]
        self.state = state
        return jnp.concatenate(out, axis=1), []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args(argv)

    from repro.configs.registry import get_config

    cfg = get_config(args.arch)
    if args.local:
        cfg = cfg.reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, ServeConfig(
        max_batch=args.batch, max_len=args.prompt_len + args.new_tokens + 8,
    ))
    shape = (
        (args.batch, args.prompt_len, cfg.n_codebooks)
        if cfg.n_codebooks else (args.batch, args.prompt_len)
    )
    prompts = jax.random.randint(jax.random.PRNGKey(1), shape, 0, cfg.vocab_size)
    out, _ = eng.prefill_and_generate(prompts, n_new=args.new_tokens)
    print(f"[serve] generated {out.shape}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
