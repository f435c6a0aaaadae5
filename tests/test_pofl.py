"""End-to-end PO-FL simulator tests (Algorithm 1) + paper-claim validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import POFLConfig, run_pofl
from repro.data import make_classification_dataset, partition_noniid_shards


def _loss_fn(params, x, y):
    logits = x @ params["w"] + params["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 3000, key)
    xt, yt = make_classification_dataset("mnist_like", 600, jax.random.PRNGKey(1))
    data = partition_noniid_shards(x, y, n_devices=20)
    params0 = {"w": jnp.zeros((784, 10)), "b": jnp.zeros((10,))}

    @jax.jit
    def ev(p):
        logits = xt @ p["w"] + p["b"]
        return _loss_fn(p, xt, yt), jnp.mean(jnp.argmax(logits, -1) == yt)

    return data, params0, ev


def _run(setup, policy, rounds=40, noise=1e-10, sampler="without_replacement", **kw):
    data, params0, ev = setup
    cfg = POFLConfig(
        n_devices=20, n_scheduled=8, policy=policy, noise_power=noise,
        sampler=sampler, **kw,
    )
    return run_pofl(_loss_fn, params0, data, cfg, rounds, eval_fn=ev, eval_every=rounds - 1)


def test_pofl_learns(setup):
    _, hist = _run(setup, "pofl")
    assert hist.test_acc[-1] > 0.85, hist.test_acc


def test_policy_ordering_matches_paper(setup):
    """Paper Figs. 3–5: channel-aware fails; PO-FL ≳ importance; noise-free
    is the upper bound. Validated at elevated noise where separation is clear."""
    accs = {}
    for policy in ["pofl", "importance", "channel", "noisefree"]:
        _, hist = _run(setup, policy, rounds=40, noise=3e-10)
        accs[policy] = hist.test_acc[-1]
    assert accs["noisefree"] >= accs["pofl"] - 0.05
    assert accs["pofl"] > accs["channel"] + 0.1
    assert accs["importance"] > accs["channel"]


def test_pofl_beats_importance_at_high_noise(setup):
    """Paper Fig. 5 noise-limited regime: PO-FL's channel term matters.
    Averaged over seeds (single-run FL accuracy is noisy)."""
    acc = {"pofl": [], "importance": []}
    ecom = {"pofl": [], "importance": []}
    for policy in acc:
        for seed in range(3):
            _, h = _run(setup, policy, rounds=40, noise=3e-9, seed=seed)
            acc[policy].append(h.test_acc[-1])
            ecom[policy].append(np.mean(h.e_com))
    assert np.mean(acc["pofl"]) > np.mean(acc["importance"]) + 0.05
    assert np.mean(ecom["pofl"]) < np.mean(ecom["importance"])


def test_ecom_decreases_with_noise_power(setup):
    _, h_low = _run(setup, "pofl", rounds=10, noise=1e-12)
    _, h_high = _run(setup, "pofl", rounds=10, noise=1e-10)
    assert np.mean(h_low.e_com) < np.mean(h_high.e_com)


def test_bernoulli_sampler_works(setup):
    _, hist = _run(setup, "pofl", sampler="bernoulli")
    assert hist.test_acc[-1] > 0.85


def test_physical_path_equivalent_training(setup):
    data, params0, ev = setup
    cfg_a = POFLConfig(n_devices=20, n_scheduled=8, policy="pofl", simulate_physical=True)
    p_a, h_a = run_pofl(_loss_fn, params0, data, cfg_a, 15, eval_fn=ev, eval_every=14)
    assert h_a.test_acc[-1] > 0.5  # the full Eq.5→8 chain also trains


def test_reproducible_given_seed(setup):
    data, params0, ev = setup
    cfg = POFLConfig(n_devices=20, n_scheduled=5, policy="pofl", seed=123)
    p1, _ = run_pofl(_loss_fn, params0, data, cfg, 5)
    p2, _ = run_pofl(_loss_fn, params0, data, cfg, 5)
    np.testing.assert_array_equal(p1["w"], p2["w"])


# -- the lane-dense gradient carry (core.grad_layout) -----------------------


def _lane_dense_lattice(setup, flat: bool, rounds: int = 3):
    """A tiny logreg lattice (one cell per policy) on ``pallas_fused`` with
    the kernel interpreted; ``flat`` forces the canonical (N, D) block.
    Returns (final params, records, the lane-dense gauge)."""
    from repro.core import grad_layout, scheduling
    from repro.obs.registry import metric_value
    from repro.sim.engine import FUSED_POLICY, SimEngine

    data, params0, _ = setup
    policies = ("pofl", "importance", "channel", "deterministic")
    cfg = POFLConfig(
        n_devices=20, n_scheduled=8, backend="pallas_fused",
        simulate_physical=True, policy=FUSED_POLICY,
    )
    params0 = {"w": jax.random.normal(jax.random.PRNGKey(3), (784, 10)) * 0.01,
               "b": params0["b"]}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_PALLAS_INTERPRET", "1")
        if flat:
            mp.setattr(grad_layout, "plan", lambda params: None)
        eng = SimEngine(_loss_fn, data, cfg)
        n = len(policies)
        states = eng.init_lattice_states(params0, jnp.arange(n, dtype=jnp.int32))
        state, recs = eng.run_lattice_chunk(
            states, jnp.arange(rounds, dtype=jnp.int32), jnp.zeros(rounds, bool),
            jnp.ones(rounds, bool), jnp.full((n,), 1e-10, jnp.float32),
            jnp.full((n,), 0.1, jnp.float32),
            jnp.asarray([scheduling.POLICY_IDS[p] for p in policies], jnp.int32),
        )
        gauge = metric_value("lattice.lane_dense_elems")
    return jax.tree.map(np.asarray, state.params), jax.tree.map(np.asarray, recs), gauge


def test_lane_dense_carry_matches_flat_block(setup):
    """The carried block and the flat block give the same round: |S^t|
    exactly, e_com, e_var, ‖ŷ‖ and the weights after 3 rounds within
    float32 summation order."""
    p_dense, r_dense, g_dense = _lane_dense_lattice(setup, flat=False)
    p_flat, r_flat, g_flat = _lane_dense_lattice(setup, flat=True)
    assert (g_dense, g_flat) == (7840, 0)
    np.testing.assert_array_equal(r_dense.n_scheduled, r_flat.n_scheduled)
    for f in ("e_com", "e_var", "grad_norm"):
        np.testing.assert_allclose(getattr(r_dense, f), getattr(r_flat, f), rtol=1e-5)
    for k in ("w", "b"):
        # elements that cancel to near zero keep the absolute rounding of
        # the leaf's scale, hence the atol at that scale
        scale = np.abs(p_flat[k]).max()
        np.testing.assert_allclose(p_dense[k], p_flat[k], rtol=1e-5, atol=1e-5 * scale)


def test_lane_dense_noise_meets_canonical_coordinates():
    """ŷ comes back in canonical order with each coordinate's own noise
    sample: where the noise dominates, the carried and the flat aggregation
    agree coordinate for coordinate."""
    from jax.flatten_util import ravel_pytree

    from repro.core import aggregation_stage, grad_layout
    from repro.core.aircomp import local_stats

    n = 6
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    grads = {"b": jax.random.normal(ks[0], (n, 10)),
             "w": jax.random.normal(ks[1], (n, 784, 10))}
    layout = grad_layout.plan({"b": grads["b"][0], "w": grads["w"][0]})
    flat = jax.vmap(lambda g: ravel_pytree(g)[0])(grads)
    rho = jnp.full((n,), 1.0 / n)
    mask = (jnp.arange(n) % 2).astype(jnp.float32)
    h = jax.random.normal(ks[2], (n,)) + 2.0 + 0j
    cfg = POFLConfig(n_devices=n, n_scheduled=3, backend="pallas_fused")

    def agg(noise, dense):
        if dense:
            blk = layout.block(grads)
            stats = grad_layout.block_stats(blk, layout.dim)
            return aggregation_stage(cfg, blk, rho, h, mask, ks[3], noise,
                                     use_pallas="interpret", stats=stats,
                                     layout=layout)[0]
        return aggregation_stage(cfg, flat, rho, h, mask, ks[3], noise,
                                 use_pallas="interpret", stats=local_stats(flat))[0]

    y_dense, y_flat = agg(1e8, True), agg(1e8, False)
    quiet = agg(0.0, False)
    assert float(jnp.max(jnp.abs(y_flat - quiet))) > 100 * float(jnp.max(jnp.abs(quiet)))
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_flat), rtol=1e-5)
    # the maps between canonical order and the segments
    v = jnp.arange(layout.dim, dtype=jnp.float32)
    seg_flat, (seg_w,) = layout.segments(v)
    np.testing.assert_array_equal(seg_flat, np.arange(10))
    assert seg_w.shape == (10, 784) and float(seg_w[3, 5]) == 10 + 5 * 10 + 3
    np.testing.assert_array_equal(layout.canonical(seg_flat, (seg_w,)), v)


@pytest.mark.parametrize("shape, carried", [
    ((784, 10), True), ((128, 10), True), ((10,), False), ((128, 128), False),
    ((127, 10), False), ((784, 200), False), ((3, 3, 32, 10), False),
])
def test_lane_dense_rule_reads_leaf_shapes(shape, carried):
    from repro.core import grad_layout

    assert grad_layout.carries_lane_dense(shape) is carried
    layout = grad_layout.plan({"b": jnp.zeros((10,)), "w": jnp.zeros(shape)})
    assert (layout is not None) is carried


def test_lane_dense_only_where_the_kernel_aggregates_one_gradient(monkeypatch):
    """Every other round keeps the flat block: the jnp backend, the CPU's
    jnp oracle, K > 1, a stateful algorithm, the traced algorithm switch
    and the model-sharded route; the CNN carries only its (128, 10) head."""
    from repro.core.pofl import ModelShard, lane_dense_layout
    from repro.models import small

    params = {"w": jnp.zeros((784, 10)), "b": jnp.zeros((10,))}
    fused = POFLConfig(backend="pallas_fused")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert lane_dense_layout(fused, params) is None  # jnp oracle on the CPU
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert lane_dense_layout(fused, params).dense_elems == 7840
    assert lane_dense_layout(POFLConfig(), params) is None
    assert lane_dense_layout(POFLConfig(backend="pallas_fused", local_algorithm="fedprox"),
                             params) is not None
    for cfg in (POFLConfig(backend="pallas_fused", local_steps=2),
                POFLConfig(backend="pallas_fused", local_algorithm="scaffold")):
        assert lane_dense_layout(cfg, params) is None
    assert lane_dense_layout(fused, params, traced_algorithm=True) is None
    assert lane_dense_layout(fused, params, model_shard=ModelShard(mesh=None)) is None
    cnn = small.init_cnn(jax.random.PRNGKey(0))
    layout = lane_dense_layout(fused, cnn)
    assert (layout.n_dense, layout.dense_elems) == (1, 1280)
