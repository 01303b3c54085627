"""Batched serving driver: prefill a batch of prompts, then decode tokens
autoregressively with the KV/SSM caches, at reduced size.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_smoke_arch
from repro.models import transformer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window override (long-context mode)")
    args = ap.parse_args()

    cfg = get_smoke_arch(args.arch)
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    prompts = jax.random.randint(
        rng, (args.batch, args.prompt_len), 0, cfg.vocab_size)

    max_len = args.prompt_len + args.gen
    state = transformer.init_decode(cfg, args.batch, max_len,
                                    window_override=args.window)

    @jax.jit
    def step(params, state, tokens):
        return transformer.decode_step(params, cfg, state, tokens,
                                       window_override=args.window)

    # prefill by teacher-forcing the prompt through the decode path
    t0 = time.time()
    tok = prompts[:, 0]
    for t in range(args.prompt_len):
        logits, state = step(params, state, prompts[:, t])
    prefill_s = time.time() - t0

    generated = []
    t0 = time.time()
    tok = jnp.argmax(logits, axis=-1)
    for _ in range(args.gen):
        generated.append(tok)
        logits, state = step(params, state, tok)
        tok = jnp.argmax(logits, axis=-1)
    gen_s = time.time() - t0

    out = np.stack([np.asarray(g) for g in generated], axis=1)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill({args.prompt_len} tok): {prefill_s:.2f}s  "
          f"decode({args.gen} tok): {gen_s:.2f}s "
          f"({args.gen * args.batch / max(gen_s, 1e-9):.1f} tok/s)")
    print("sample generations (token ids):")
    for b in range(min(args.batch, 2)):
        print(f"  seq{b}: {out[b].tolist()}")


if __name__ == "__main__":
    main()
