"""End-to-end driver: train a ~100M-param LM for a few hundred steps on CPU.

Uses the stablelm-3b family at width 512 (≈114M params), the synthetic
Zipf-Markov token stream, the full production train step (microbatching,
AdamW, grad clip, z-loss) and the restartable checkpointing loop.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import configure_compile_cache
from repro.configs import TrainConfig, get_config
from repro.data.tokens import SyntheticTokenStream
from repro.models import get_api
from repro.train import adamw_init, build_train_step
from repro.train.fault_tolerance import RestartableLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    configure_compile_cache()

    # ~100M-param member of the stablelm family
    cfg = get_config("stablelm-3b").replace(
        n_layers=10, d_model=512, n_heads=8, n_kv_heads=8, head_dim=64,
        d_ff=2048, vocab_size=50304)
    tcfg = TrainConfig(seq_len=args.seq, global_batch=args.batch,
                       learning_rate=1e-3, warmup_steps=30,
                       total_steps=args.steps, compute_dtype="float32",
                       remat="none")

    api = get_api(cfg)
    params = api.init_params(jax.random.key(0), cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n/1e6:.1f}M params ({cfg.n_layers}L d={cfg.d_model})")

    opt = adamw_init(params)
    step_jit = jax.jit(build_train_step(cfg, tcfg))
    stream = SyntheticTokenStream(cfg.vocab_size, seed=0)

    def step_fn(state, batch):
        p, o = state
        p, o, m = step_jit(p, o, batch)
        return (p, o), m

    def data_fn(step):
        b = stream.batch_at(step, args.batch, args.seq)
        return {k: jnp.asarray(v) for k, v in b.items()}

    loop = RestartableLoop(step_fn, data_fn, args.ckpt_dir, ckpt_every=100)
    t0 = time.time()
    _, step, log = loop.run((params, opt), args.steps)
    dt = time.time() - t0

    for rec in log[::25]:
        print(f"  step {rec['step']:4d}  loss {rec['loss']:.4f}  "
              f"{rec['sec']*1e3:.0f} ms/step")
    print(f"trained {step} steps in {dt:.1f}s — "
          f"loss {log[0]['loss']:.3f} -> {log[-1]['loss']:.3f}")
    assert log[-1]["loss"] < log[0]["loss"], "loss must decrease"


if __name__ == "__main__":
    main()
