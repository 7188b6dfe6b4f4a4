"""Train a qwen2-family LM with the full production substrate:
deterministic sharded data, AdamW + cosine schedule, checkpoint/restart
(kill it mid-run and re-launch — it resumes), straggler monitoring.

Default is a ~15M-param config so a few hundred steps finish on CPU; pass
``--arch qwen2-0.5b --full`` on a real accelerator for the 0.5B run.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.models.transformer import LMConfig
from repro.optim import AdamWConfig, cosine_with_warmup
from repro.train.steps import init_train_state, build_lm_train_step
from repro.data import tokens as tok
from repro.ckpt import checkpoint as ckpt
from repro.ft.straggler import StepTimer
from repro.launch.compile_cache import configure_compile_cache


def small_cfg():
    return LMConfig(name="qwen2-mini", n_layers=4, d_model=256, n_heads=8,
                    n_kv_heads=2, head_dim=32, d_ff=1024, vocab=4096,
                    qkv_bias=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-mini")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    configure_compile_cache()

    if args.arch == "qwen2-mini":
        cfg = small_cfg()
    else:
        cfg = registry.lm_config(args.arch, reduced=not args.full)
    ocfg = AdamWConfig(lr=3e-4)
    sched = functools.partial(cosine_with_warmup, peak_lr=ocfg.lr,
                              warmup_steps=20, total_steps=args.steps)
    step_fn = jax.jit(build_lm_train_step(cfg, ocfg, schedule=sched))

    state = init_train_state(jax.random.key(0), cfg, ocfg)
    n_params = sum(int(x.size) for x in jax.tree.leaves(state.params))
    print(f"[model] {cfg.name}: {n_params / 1e6:.1f}M params")

    latest = ckpt.latest_step(args.ckpt)
    start = 0
    if latest is not None:
        state = ckpt.restore(args.ckpt, latest, state)
        start = latest + 1
        print(f"[resume] from step {latest}")

    timer = StepTimer()
    for step in range(start, args.steps):
        batch = jnp.asarray(tok.shard_for(step, 0, 1,
                                          global_batch=args.batch,
                                          seq_len=args.seq,
                                          vocab=cfg.vocab, seed=0))
        timer.start()
        state, metrics = step_fn(state, batch)
        dt = timer.stop()
        if timer.is_straggler(dt):
            print(f"[straggler] step {step} took {dt:.2f}s "
                  f"(median {timer.median:.2f}s)")
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {float(metrics['loss']):.4f}  "
                  f"{dt:.2f}s/step")
        if (step + 1) % args.ckpt_every == 0 or step == args.steps - 1:
            ckpt.save(args.ckpt, step, state, keep=2)
    print("[done]")


if __name__ == "__main__":
    main()
