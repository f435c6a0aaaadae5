"""The benchmark's own datasets and initial weights, drawn from seeds.

A copy of the seeded MNIST- and CIFAR-shaped generators and of the
sort-by-label 2-shard non-IID partition the program ships in
``repro.data.synthetic`` and ``repro.data.partition``, kept here so that a
later change to the program's data code cannot move the yardstick. The
harness hands these arrays to the program (``DeviceData``, ``TaskEval``,
``run_lattice``) and the same arrays to the plain reference.

The dataset comes from the configuration's fixed ``data_seed``; the run's
``--seed`` drives only the initial weights and the lattice seeds (see
``perfbench/programs/lattice.py`` for why).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("kind", "n_samples", "dim", "n_classes", "channel_bias"))
def classification_dataset(kind: str, n_samples: int, key, dim: int,
                           n_classes: int = 10, noise: float = 0.8,
                           proto_seed: int = 42, channel_bias: float = 0.0):
    """Gaussian class clusters around prototypes fixed by ``proto_seed``.

    ``kind`` is ``"mnist_like"`` (flat ``(n, dim)`` features) or
    ``"cifar_like"`` (``(n, 32, 32, 3)`` images, ``dim`` = 3,072).
    """
    shape = (dim,) if kind == "mnist_like" else (32, 32, 3)
    _, k_label, k_noise, k_scale = jax.random.split(key, 4)
    k_proto = jax.random.PRNGKey(proto_seed)
    prototypes = jax.random.normal(k_proto, (n_classes, dim)) / jnp.sqrt(dim)
    labels = jax.random.randint(k_label, (n_samples,), 0, n_classes)
    eps = jax.random.normal(k_noise, (n_samples, dim)) / jnp.sqrt(dim)
    scale = 1.0 + 0.3 * jax.random.normal(k_scale, (n_samples, 1))
    feats = (scale * (prototypes[labels] + noise * eps)).reshape((n_samples,) + shape)
    if channel_bias:
        k_bias = jax.random.split(k_proto)[1]
        bias = jax.random.normal(k_bias, (n_classes, shape[-1]))
        feats = feats + channel_bias * bias[labels][:, None, None, :]
    return feats.astype(jnp.float32), labels.astype(jnp.int32)


def noniid_shards(features, labels, n_devices: int, shards_per_device: int,
                  seed: int):
    """Sort by label, cut ``n_devices * shards_per_device`` equal shards and
    deal each device ``shards_per_device`` of them -> stacked ``(N, m, ...)``
    features and ``(N, m)`` labels."""
    features, labels = np.asarray(features), np.asarray(labels)
    n_shards = n_devices * shards_per_device
    shard_size = labels.shape[0] // n_shards
    order = np.argsort(labels, kind="stable")
    rng = np.random.default_rng(seed)
    shard_ids = rng.permutation(n_shards)
    feats, labs = [], []
    for d in range(n_devices):
        idx = np.concatenate([
            order[s * shard_size:(s + 1) * shard_size]
            for s in shard_ids[d * shards_per_device:(d + 1) * shards_per_device]
        ])
        rng.shuffle(idx)
        feats.append(features[idx])
        labs.append(labels[idx])
    return np.stack(feats), np.stack(labs)


TASKS = ("logreg", "cnn")  # the lattice's tasks, each with its own data and weights


def check_task(config: dict) -> None:
    """Refuse a ``task`` the lattice does not run, rather than reading it
    as another task's."""
    if config.get("task") not in TASKS:
        raise ValueError(
            f"unknown lattice task {config.get('task')!r}; known: {list(TASKS)}"
        )


def make_dataset(config: dict):
    """``(train_x (N, m, ...), train_y (N, m), test_x, test_y)`` as numpy
    arrays, from the configuration's sizes and ``data_seed``."""
    check_task(config)
    kind = "mnist_like" if config["task"] == "logreg" else "cifar_like"
    dim = int(np.prod(config["input_shape"]))
    key = jax.random.PRNGKey(config["data_seed"])
    k_train, k_test, _ = jax.random.split(key, 3)
    bias = float(config.get("channel_bias", 0.0))
    x_tr, y_tr = classification_dataset(
        kind, config["n_train"], k_train, dim, config["n_classes"],
        channel_bias=bias,
    )
    x_te, y_te = classification_dataset(
        kind, config["n_test"], k_test, dim, config["n_classes"],
        channel_bias=bias,
    )
    fx, fy = noniid_shards(
        x_tr, y_tr, config["n_devices"], config["classes_per_device"],
        config["data_seed"],
    )
    return fx, fy, np.asarray(x_te), np.asarray(y_te)


def param_shapes(config: dict) -> dict:
    """Parameter shapes by name, the dict-of-dicts layout the program's
    models take (``w``/``b`` per layer)."""
    check_task(config)
    n_cls = config["n_classes"]
    if config["task"] == "logreg":
        d = int(np.prod(config["input_shape"]))
        return {"w": (d, n_cls), "b": (n_cls,)}
    shapes, c_prev = {}, config["input_shape"][-1]
    k = config["kernel_size"]
    for i, c in enumerate(config["conv_channels"]):
        shapes[f"conv{i}"] = {"w": (k, k, c_prev, c), "b": (c,)}
        c_prev = c
    shapes["fc1"] = {"w": (c_prev, config["hidden"]), "b": (config["hidden"],)}
    shapes["out"] = {"w": (config["hidden"], n_cls), "b": (n_cls,)}
    return shapes


def init_params(config: dict, seed_key):
    """Initial weights from the run's seed, on the device, in one jitted
    call: logistic regression N(0, 0.01²) weights and zero bias; the CNN
    He-normal convolutions and dense layers, zero biases."""
    shapes = param_shapes(config)

    @jax.jit
    def make(key):
        if config["task"] == "logreg":
            return {
                "w": jax.random.normal(key, shapes["w"]) * 0.01,
                "b": jnp.zeros(shapes["b"]),
            }
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, s) in zip(keys, shapes.items()):
            w = s["w"]
            fan_in = int(np.prod(w[:-1]))
            gain = 1.0 if name == "out" else 2.0
            out[name] = {
                "w": jax.random.normal(k, w) * jnp.sqrt(gain / fan_in),
                "b": jnp.zeros(s["b"]),
            }
        return out

    return make(seed_key)
