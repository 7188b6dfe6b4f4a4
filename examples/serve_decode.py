"""Serve a small LM with batched decode against a ring KV cache.

    PYTHONPATH=src python examples/serve_decode.py --tokens 32 --batch 4
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.transformer import LMConfig
from repro.launch.compile_cache import configure_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--cache", type=int, default=128)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = LMConfig(name="gemma3-mini", n_layers=4, d_model=128, n_heads=8,
                   n_kv_heads=4, head_dim=16, d_ff=512, vocab=2048,
                   act="geglu", local_global=(3, 16))
    params = T.lm_init(jax.random.key(0), cfg)
    serve = jax.jit(lambda p, t, c, i: T.serve_step(p, cfg, t, c, i))

    caches = T.init_cache(cfg, batch=args.batch, max_len=args.cache)
    toks = jax.random.randint(jax.random.key(1), (args.batch, 1), 0,
                              cfg.vocab)
    out = []
    t0 = time.time()
    for i in range(args.tokens):
        logits, caches = serve(params, toks, caches, jnp.int32(i))
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(toks[:, 0])
    dt = time.time() - t0
    seqs = jnp.stack(out, axis=1)
    print(f"decoded {args.batch}x{args.tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s)")
    for b in range(args.batch):
        print(f"  seq[{b}]: {seqs[b, :16].tolist()} ...")


if __name__ == "__main__":
    main()
