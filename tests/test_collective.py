"""The AirComp noisy all-reduce (shard_map) must agree with the reference
aggregation in core/aircomp.py. Runs on a virtual multi-device CPU mesh —
conftest does NOT set XLA_FLAGS globally, so this module spawns a subprocess
with 8 virtual devices for the mesh test and runs in-process checks on 1."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aircomp, collective


def test_aircomp_allreduce_single_device_semantics():
    """On a 1-device 'mesh' the psum is identity: check weighting+noise math."""
    g = {"w": jnp.arange(8.0), "b": jnp.ones((3,))}
    key = jax.random.PRNGKey(0)
    out = collective.aircomp_allreduce(g, jnp.asarray(2.0), jnp.asarray(0.0), key, ())
    np.testing.assert_allclose(out["w"], 2.0 * g["w"])
    np.testing.assert_allclose(out["b"], 2.0 * g["b"])


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import aircomp, collective
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1)  # (data=8, model=1)
    n, dim = 8, 64
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    g = jax.random.normal(k1, (n, dim))
    h = (jax.random.normal(k2, (n,)) + 1j*jax.random.normal(k3, (n,)))/jnp.sqrt(2)
    rho = jnp.linspace(0.05, 0.2, n)
    mask = (jnp.arange(n) % 2 == 0).astype(jnp.float32)

    # reference (single-host Eq.16 path)
    noise_key = jax.random.PRNGKey(5)
    y_ref, _ = aircomp.aircomp_aggregate(
        g, rho, h, mask, noise_key, 1.0, 1e-4, simulate_physical=False)

    # distributed twin: coeffs = mask*rho, noise_amp = sqrt(V_g)/a
    stats = aircomp.local_stats(g)
    _, v_g = aircomp.global_stats(stats, rho, mask)
    a = aircomp.denoise_scalar(rho, jnp.abs(h), mask, 1.0)
    amp = jnp.sqrt(v_g)/a

    with jax.set_mesh(mesh):
        agg = collective.make_sharded_aggregator(mesh, "data")
        y_dist = agg(g, mask*rho, jnp.asarray(0.0), jax.random.PRNGKey(5))
    # zero-noise comparison isolates the weighted psum
    y_ref0, _ = aircomp.aircomp_aggregate(
        g, rho, h, mask, noise_key, 1.0, 0.0, simulate_physical=False)
    np.testing.assert_allclose(np.asarray(y_dist), np.asarray(y_ref0), rtol=1e-5, atol=1e-6)
    print("OK")
    """
)


def test_sharded_aggregator_matches_reference_on_8dev_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout
