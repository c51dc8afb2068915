"""Batched serving example: prefill + decode with KV-cache/SSM state across
the model zoo (deployment leg of the paper's create/train/deploy triad).

    PYTHONPATH=src python examples/serve_lm.py --arch smollm-135m
    PYTHONPATH=src python examples/serve_lm.py --arch xlstm-1.3b   # recurrent-state serving
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax

from repro.configs.registry import get_config
from repro.models.model import init_params
from repro.launch.serve import Engine, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()  # reduced weights: CPU-friendly demo
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    scfg = ServeConfig(
        max_batch=args.batch,
        max_len=args.prompt_len + args.new_tokens + 8,
        temperature=args.temperature,
    )
    eng = Engine(cfg, params, scfg)

    shape = (
        (args.batch, args.prompt_len, cfg.n_codebooks)
        if cfg.n_codebooks
        else (args.batch, args.prompt_len)
    )
    prompts = jax.random.randint(jax.random.PRNGKey(1), shape, 0, cfg.vocab_size)
    t0 = time.time()
    out, _ = eng.prefill_and_generate(prompts, n_new=args.new_tokens)
    dt = time.time() - t0
    total_new = args.batch * args.new_tokens
    print(f"arch={cfg.name} family={cfg.family}")
    print(f"generated {out.shape} tokens in {dt:.2f}s  ({total_new/dt:.1f} tok/s batched)")
    print("first sequence:", out[0].tolist()[:12], "...")


if __name__ == "__main__":
    main()
