"""End-to-end driver: PO-FL training of a ~100M-parameter language model on
a (CPU-host) mesh for a few hundred rounds — the distributed trainer stack
(launch/train.py) exercised for real, not just dry-run.

Default is a quick CPU-sized run; --rounds 200 --dmodel 768 --layers 12
reaches the ~100M-parameter scale of the deliverable (slow on CPU).

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_pofl_lm.py --rounds 30
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.data.synthetic import make_token_dataset
from repro.launch.mesh import make_host_mesh
from repro.launch.train import POFLTrainer, TrainerConfig, run_training
from repro.models.config import InputShape
from repro.optim.optimizers import adamw, cosine_schedule
from repro.sim.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--dmodel", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--policy", default="pofl")
    ap.add_argument("--arch", default="qwen2-0.5b",
                    help="architecture family to scale down")
    args = ap.parse_args()
    enable_compile_cache(CHECKOUT_CACHE_DIR)

    cfg = configs.base_config(args.arch)
    cfg = dataclasses.replace(
        cfg, n_layers=args.layers, d_model=args.dmodel,
        n_heads=max(4, args.dmodel // 64), n_kv_heads=max(2, args.dmodel // 128),
        d_ff=args.dmodel * 4, vocab_size=4096, tie_embeddings=True,
    )
    print(f"model: {cfg.name} family, {cfg.param_count()/1e6:.1f}M params")

    shape = InputShape("lm", seq_len=args.seq, global_batch=args.batch, kind="train")
    mesh = make_host_mesh(model=1)
    n_fl = mesh.shape["data"]
    print(f"mesh: {dict(mesh.shape)}  ({n_fl} FL devices)")

    trainer = POFLTrainer(
        cfg, shape, mesh,
        TrainerConfig(policy=args.policy, n_scheduled=max(1, n_fl // 2),
                      noise_power=1e-10, stats_mode="sketch", n_probes=2),
        optimizer=adamw(cosine_schedule(3e-4, args.rounds, warmup=10)),
    )

    tokens = make_token_dataset(
        args.batch * 8, args.seq, cfg.vocab_size, jax.random.PRNGKey(0)
    )

    def batch_fn(t):
        idx = jnp.arange(args.batch) + (t * args.batch) % (args.batch * 7)
        return {"tokens": tokens[idx]}

    _, _, losses = run_training(trainer, batch_fn, args.rounds)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "training did not descend"


if __name__ == "__main__":
    main()
