"""Serving example: batched prefill + autoregressive decode on a mesh,
using the sharded serve_step (KV cache: batch × data, sequence × model).

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/serve_decode.py
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import Server
from repro.models import api
from repro.models.cache import pad_cache
from repro.models.config import InputShape
from repro.sim.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache(CHECKOUT_CACHE_DIR)

    cfg = configs.reduced_config(args.arch)
    cfg = dataclasses.replace(cfg, n_layers=4)
    mesh = make_host_mesh(model=1)
    total = args.prompt_len + args.new_tokens
    shape = InputShape("serve", seq_len=total, global_batch=args.batch, kind="decode")

    params = api.model_init(cfg, jax.random.PRNGKey(0))
    prompt = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
        )
    }
    if cfg.arch_type == "vlm":
        prompt["embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.vlm.n_patches, cfg.d_model)
        )
    if cfg.arch_type == "encdec":
        prompt["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.encdec.n_enc_frames, cfg.d_model)
        )

    logits, cache = api.model_prefill(params, cfg, prompt, jnp.float32)
    cache = pad_cache(cache, total)
    first = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)

    server = Server(cfg, shape, mesh, dtype=jnp.float32)
    p_sh = server.load_params(params)
    toks, _ = server.decode(
        p_sh, first, cache, start_t=args.prompt_len, n_tokens=args.new_tokens
    )
    print(f"arch={args.arch}  decoded {toks.shape} tokens")
    print("sample:", toks[0].tolist())


if __name__ == "__main__":
    main()
