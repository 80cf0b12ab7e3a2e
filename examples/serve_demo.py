"""Batched serving demo: prefill a batch of prompts token-by-token into the
KV cache, then greedy-decode continuations -- the serve_step the decode
dry-run cells lower, at smoke scale.

    PYTHONPATH=src python examples/serve_demo.py [--arch qwen3_1_7b]
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.cache import use_compile_cache
from repro.models import LM


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=20)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    B = args.batch
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (B, args.prompt_len)),
                          jnp.int32)

    step = jax.jit(model.decode_step)
    cache = model.init_cache(B, args.prompt_len + args.gen)

    # prefill by stepping the prompt (cache warmup)
    t0 = time.time()
    for t in range(args.prompt_len):
        logits, cache = step(params, prompts[:, t: t + 1],
                             jnp.full((B,), t, jnp.int32), cache)
    t_prefill = time.time() - t0

    # greedy decode
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.full((B,), args.prompt_len + i, jnp.int32)
        logits, cache = step(params, tok, pos, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.time() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={B}")
    print(f"prefill {args.prompt_len} tok: {t_prefill:.2f}s; "
          f"decode {args.gen} tok: {t_dec:.2f}s "
          f"({B * args.gen / max(t_dec, 1e-9):.1f} tok/s batched)")
    print("generated token ids (first sequence):",
          np.asarray(gen[0])[:16].tolist())


if __name__ == "__main__":
    main()
