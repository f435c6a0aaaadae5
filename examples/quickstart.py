"""Quickstart: the PO-FL framework in ~60 lines.

Trains a logistic-regression model over 30 simulated wireless devices with
over-the-air (AirComp) gradient aggregation, comparing the paper's channel
and gradient-importance aware scheduling against a channel-aware baseline.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core.channel import ChannelConfig
from repro.core.pofl import POFLConfig, run_pofl
from repro.data.partition import partition_noniid_shards
from repro.data.synthetic import make_classification_dataset
from repro.models import small
from repro.sim.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


def main():
    enable_compile_cache(CHECKOUT_CACHE_DIR)
    # 1. data: synthetic MNIST-like, non-IID 2-classes-per-device shards
    key = jax.random.PRNGKey(0)
    k_train, k_test, k_init = jax.random.split(key, 3)
    x_tr, y_tr = make_classification_dataset("mnist_like", 3000, k_train)
    x_te, y_te = make_classification_dataset("mnist_like", 1000, k_test)
    data = partition_noniid_shards(x_tr, y_tr, n_devices=30)

    # 2. model: logistic regression (the paper's convex case)
    params0 = small.init_logreg(k_init)
    eval_fn = small.make_eval_fn(small.logreg_logits, small.logreg_loss, x_te, y_te)

    # 3. train under two scheduling policies
    for policy in ("pofl", "channel"):
        cfg = POFLConfig(policy=policy, n_scheduled=10, noise_power=1e-10)
        _, hist = run_pofl(
            small.logreg_loss, params0, data, cfg, n_rounds=30,
            eval_fn=eval_fn, eval_every=5,
            channel_cfg=ChannelConfig(n_devices=30, noise_power=1e-10),
        )
        print(f"policy={policy:>8s}  acc: "
              + " ".join(f"{a:.3f}" for a in hist.test_acc))


if __name__ == "__main__":
    main()
