"""repro.sim subsystem tests: engine↔run_pofl trajectory equivalence,
engine caching / retrace guards, aggregation-backend parity, heterogeneous
(Dirichlet-sized) shards, channel-scenario statistics, Dirichlet partition,
lattice records, and the trial-batched fused kernel."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DeviceData, POFLConfig, make_round_step, run_pofl
from repro.core.channel import ChannelConfig, ChannelState
from repro.data import (
    dirichlet_sizes,
    make_classification_dataset,
    partition_dirichlet,
    partition_dirichlet_mixed,
    partition_dirichlet_sized,
    partition_noniid_shards,
)
from repro.kernels.aircomp import aircomp_fused_batch, aircomp_fused_batch_ref
from repro.sim import (
    LatticeSpec,
    SimEngine,
    cached_engine,
    engine_cache_stats,
    make_channel_process,
    run_lattice,
)


def _loss_fn(params, x, y):
    logits = x @ params["w"] + params["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 1200, key)
    data = partition_noniid_shards(x, y, n_devices=12)
    params0 = {"w": jnp.zeros((784, 10)), "b": jnp.zeros((10,))}

    def ev(p):
        logits = x[:400] @ p["w"] + p["b"]
        return _loss_fn(p, x[:400], y[:400]), jnp.mean(jnp.argmax(logits, -1) == y[:400])

    return data, params0, ev


# --------------------------------------------------------------------------
# engine ↔ run_pofl equivalence (acceptance criterion: ≤1e-5 on static fading)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["pofl", "deterministic"])
def test_engine_matches_legacy_round_loop(setup, policy):
    """The scanned engine must reproduce the historical per-round-jit Python
    loop (the seed repo's run_pofl) for identical seeds on static fading."""
    data, params0, ev = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, policy=policy, seed=3)
    n_rounds = 8

    # legacy loop: per-round jit, key chain advanced in Python
    key = jax.random.PRNGKey(cfg.seed)
    k_chan_init, key = jax.random.split(key)
    channel = ChannelState.create(
        ChannelConfig(
            n_devices=12, tx_power=cfg.tx_power, noise_power=cfg.noise_power
        ),
        k_chan_init,
    )
    step = make_round_step(_loss_fn, data, channel, cfg)
    params = params0
    e_coms = []
    for t in range(n_rounds):
        key, k_round = jax.random.split(key)
        params, m = step(params, k_round, jnp.asarray(t, jnp.float32))
        e_coms.append(float(m.e_com))

    # scanned engine (via the run_pofl wrapper)
    engine = SimEngine(_loss_fn, data, cfg)
    params_sim, hist = engine.run_with_history(params0, n_rounds, eval_fn=ev)
    np.testing.assert_allclose(
        np.asarray(params_sim["w"]), np.asarray(params["w"]), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(np.asarray(hist.e_com), e_coms, rtol=1e-5)
    assert hist.test_round[-1] == n_rounds - 1


def test_run_with_history_matches_plain_chunks(setup):
    """Eval chunking must not perturb the trajectory: same params with and
    without an eval_fn."""
    data, params0, ev = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, seed=11)
    engine = SimEngine(_loss_fn, data, cfg)
    p_eval, _ = engine.run_with_history(params0, 7, eval_fn=ev, eval_every=3)
    p_plain, hist = engine.run_with_history(params0, 7, eval_fn=None)
    np.testing.assert_array_equal(np.asarray(p_eval["w"]), np.asarray(p_plain["w"]))
    assert len(hist.e_com) == 7 and hist.test_round == []


# --------------------------------------------------------------------------
# channel scenarios
# --------------------------------------------------------------------------


def _rollout(proc, key, n_rounds):
    state = proc.init(jax.random.PRNGKey(0))

    def body(st, k):
        st, h, avail = proc.step(st, k)
        return st, (h, avail)

    _, (hs, avails) = jax.lax.scan(body, state, jax.random.split(key, n_rounds))
    return hs, avails  # each (n_rounds, n_devices)


def test_gauss_markov_stationary_moments():
    """h_t must stay CN(0, g_i): E[h]≈0, E[|h|²]≈g_i, and lag-1 autocorr≈ρ."""
    cfg = ChannelConfig(n_devices=6)
    proc = make_channel_process("gauss_markov", cfg, corr=0.8)
    gains = proc.init(jax.random.PRNGKey(0))[0]
    hs, avails = _rollout(proc, jax.random.PRNGKey(1), 4000)
    assert np.asarray(avails).all()  # gauss_markov never drops devices

    emp_power = jnp.mean(jnp.abs(hs) ** 2, axis=0)
    np.testing.assert_allclose(np.asarray(emp_power), np.asarray(gains), rtol=0.15)
    emp_mean = np.abs(np.asarray(jnp.mean(hs, axis=0)))
    assert emp_mean.max() < 0.15 * float(jnp.sqrt(gains.max()))

    lag1 = jnp.mean(hs[1:] * jnp.conj(hs[:-1]), axis=0)
    rho_hat = np.asarray(jnp.real(lag1) / emp_power)
    np.testing.assert_allclose(rho_hat, 0.8, atol=0.1)


def test_static_rayleigh_matches_channelstate():
    """The registry's static scenario is bit-identical to core ChannelState."""
    cfg = ChannelConfig(n_devices=8)
    proc = make_channel_process("static_rayleigh", cfg)
    state = proc.init(jax.random.PRNGKey(5))
    legacy = ChannelState.create(cfg, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(legacy.gains))
    _, h, avail = proc.step(state, jax.random.PRNGKey(9))
    np.testing.assert_array_equal(
        np.asarray(h), np.asarray(legacy.sample(jax.random.PRNGKey(9)))
    )
    np.testing.assert_array_equal(np.asarray(avail), 1.0)


def test_mobility_distances_stay_in_cell():
    cfg = ChannelConfig(n_devices=5, d_min=10.0, d_max=50.0)
    proc = make_channel_process("mobility", cfg, speed=30.0)
    state = proc.init(jax.random.PRNGKey(0))
    for i in range(50):
        state, _, _ = proc.step(state, jax.random.fold_in(jax.random.PRNGKey(1), i))
        d = np.asarray(state[0])
        assert (d >= cfg.d_min - 1e-4).all() and (d <= cfg.d_max + 1e-4).all()


def test_dropout_marks_devices_unavailable():
    cfg = ChannelConfig(n_devices=32)
    proc = make_channel_process("dropout", cfg, p_drop=0.3)
    base = make_channel_process("static_rayleigh", cfg)
    st_d = proc.init(jax.random.PRNGKey(0))
    st_b = base.init(jax.random.PRNGKey(0))
    k = jax.random.PRNGKey(7)
    _, h_d, avail = proc.step(st_d, k)
    # the base fading trajectory is untouched (k_base = split(k)[0])
    k_base, _ = jax.random.split(k)
    _, h_b, _ = base.step(st_b, k_base)
    np.testing.assert_array_equal(np.asarray(h_d), np.asarray(h_b))
    avail = np.asarray(avail)
    assert set(np.unique(avail)) <= {0.0, 1.0}
    assert 0 < (avail == 0).sum() < 32  # some but not all dropped at p=0.3

    _, avails = _rollout(proc, jax.random.PRNGKey(3), 2000)
    drop_rate = 1.0 - float(np.mean(np.asarray(avails)))
    np.testing.assert_allclose(drop_rate, 0.3, atol=0.03)


def test_sampler_clamps_when_fewer_selectable_than_s():
    """Zero-prob (unavailable) devices are never drafted and never weighted:
    with 3 selectable devices and |S|=4 the realized schedule is exactly the
    3 selectable ones, surplus draws are -1 sentinels, and the Eq. 37
    weights stay finite and zero off the selectable set."""
    from repro.core import scheduling

    probs = jnp.array([0.5, 0.3, 0.2] + [0.0] * 9)
    data_frac = jnp.full((12,), 1.0 / 12)
    for seed in range(5):
        sched = scheduling.sample_without_replacement(
            jax.random.PRNGKey(seed), probs, 4
        )
        mask = np.asarray(sched.mask)
        np.testing.assert_array_equal(mask[:3], 1.0)
        np.testing.assert_array_equal(mask[3:], 0.0)
        assert (np.asarray(sched.indices) == -1).sum() == 1
        rho = np.asarray(
            scheduling.aggregation_weights(sched, probs, data_frac, 4)
        )
        assert np.isfinite(rho).all()
        np.testing.assert_array_equal(rho[3:], 0.0)
        assert (rho[:3] > 0).all()


def test_dropout_empty_rounds_finite_on_physical_path(setup):
    """Rounds where every device drops must not NaN the Eq. 5→8 physical
    chain (a=inf, rho=0 would give 0·inf transmit scalars without the
    mask-before-multiply guard in aircomp_aggregate)."""
    data, params0, _ = setup
    cfg = POFLConfig(
        n_devices=12, n_scheduled=3, policy="pofl", seed=0,
        simulate_physical=True,
    )
    engine = SimEngine(
        _loss_fn, data, cfg, scenario="dropout",
        scenario_params={"p_drop": 0.85},
    )
    state = engine.init(params0, 0)
    final, recs = jax.jit(
        lambda s: engine.scan_rounds(
            s, jnp.arange(50, dtype=jnp.int32), jnp.zeros(50, bool)
        )
    )(state)
    assert (np.asarray(recs.n_scheduled) == 0).any()  # empty rounds occurred
    assert np.isfinite(np.asarray(final.params["w"])).all()
    assert np.isfinite(np.asarray(recs.grad_norm)).all()


def test_dropout_rounds_stay_finite(setup):
    """Even in rounds where dropout leaves fewer than |S| devices available,
    the engine's trajectory and metrics stay finite (|S| clamps)."""
    data, params0, _ = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, policy="pofl", seed=0)
    engine = SimEngine(
        _loss_fn, data, cfg, scenario="dropout",
        # p_drop=0.75: P(<4 of 12 available) ≈ 0.65 per round, so the
        # clamping path definitely fires within 40 rounds
        scenario_params={"p_drop": 0.75},
    )
    state = engine.init(params0, 0)
    final, recs = jax.jit(
        lambda s: engine.scan_rounds(
            s, jnp.arange(40, dtype=jnp.int32), jnp.zeros(40, bool)
        )
    )(state)
    n_sched = np.asarray(recs.n_scheduled)
    assert np.isfinite(np.asarray(recs.e_com)).all()
    assert np.isfinite(np.asarray(recs.e_var)).all()
    assert np.isfinite(np.asarray(jax.tree.leaves(final.params)[0])).all()
    assert (n_sched <= 4).all() and n_sched.min() < 4  # clamping observed


# --------------------------------------------------------------------------
# dirichlet partition
# --------------------------------------------------------------------------


def test_dirichlet_partition_shapes_and_skew():
    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 2000, key)
    n_dev = 10
    skewed = partition_dirichlet(x, y, n_dev, beta=0.1, seed=0)
    near_iid = partition_dirichlet(x, y, n_dev, beta=1000.0, seed=0)

    per = 2000 // n_dev
    assert skewed.features.shape == (n_dev, per, 784)
    assert skewed.labels.shape == (n_dev, per)

    def mean_top_class_frac(dd):
        fracs = []
        for d in range(n_dev):
            _, counts = np.unique(np.asarray(dd.labels[d]), return_counts=True)
            fracs.append(counts.max() / counts.sum())
        return float(np.mean(fracs))

    # β→0 concentrates mass on few classes; β→∞ recovers ~uniform (10
    # classes → top frac ≈ 0.1–0.2). The equal-size constraint dilutes the
    # skew for late devices (class pools run dry), so ~0.4 is the realistic
    # concentrated value, still far from uniform.
    assert mean_top_class_frac(skewed) > 0.35
    assert mean_top_class_frac(near_iid) < 0.25
    assert mean_top_class_frac(skewed) > mean_top_class_frac(near_iid) + 0.15
    # no sample is duplicated across devices: the per-class totals over all
    # shards can then never exceed the global per-class counts (and with
    # M divisible by N they must match exactly)
    global_classes, global_counts = np.unique(np.asarray(y), return_counts=True)
    part_classes, part_counts = np.unique(
        np.asarray(skewed.labels).ravel(), return_counts=True
    )
    np.testing.assert_array_equal(part_classes, global_classes)
    np.testing.assert_array_equal(part_counts, global_counts)
    # ...and the feature rows themselves are all distinct (continuous
    # features are unique w.p. 1, so any duplicate row = a reused sample)
    flat = np.asarray(skewed.features).reshape(n_dev * per, -1)
    assert np.unique(flat, axis=0).shape[0] == n_dev * per


# --------------------------------------------------------------------------
# lattice records
# --------------------------------------------------------------------------


def test_lattice_record_shapes_and_axes(setup):
    data, params0, ev = setup
    spec = LatticeSpec(
        policies=("pofl", "channel"),
        noise_powers=(1e-11, 1e-9),
        alphas=(0.1, 1.0),
        seeds=(0, 1000, 2000),
        n_rounds=6,
        eval_every=2,
    )
    recs = run_lattice(
        _loss_fn, data, params0, spec,
        base_cfg=POFLConfig(n_devices=12, n_scheduled=4),
        eval_fn=ev,
    )
    assert recs.e_com.shape == (1, 2, 2, 2, 3, 6)  # leading algorithm axis
    np.testing.assert_array_equal(recs.eval_rounds, [0, 2, 4, 5])
    assert recs.acc.shape == (1, 2, 2, 2, 3, 4)
    assert np.isfinite(recs.e_com).all() and np.isfinite(recs.acc).all()
    assert (recs.n_scheduled >= 1).all()

    c = recs.cell(policy="pofl", noise_power=1e-9, alpha=1.0)
    assert c["acc"].shape == (1, 3, 4)  # un-named algorithm axis stays (size 1)
    with pytest.raises(ValueError):
        recs.cell(nonsense=3)


def test_lattice_single_cell_matches_run_pofl(setup):
    """A 1-cell lattice is the engine run end-to-end: accuracies must match
    run_pofl (which shares the engine) exactly in eval rounds and closely in
    values (eval inside scan vs on host)."""
    from repro.core import run_pofl

    data, params0, ev = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, policy="pofl", seed=0)
    spec = LatticeSpec(policies=("pofl",), seeds=(0,), n_rounds=6, eval_every=2)
    recs = run_lattice(
        _loss_fn, data, params0, spec, base_cfg=cfg, eval_fn=jax.jit(ev)
    )
    _, hist = run_pofl(_loss_fn, params0, data, cfg, 6, eval_fn=jax.jit(ev), eval_every=2)
    np.testing.assert_array_equal(recs.eval_rounds, hist.test_round)
    np.testing.assert_allclose(
        recs.acc[0, 0, 0, 0, 0], hist.test_acc, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        recs.e_com[0, 0, 0, 0, 0], hist.e_com, rtol=1e-5
    )


def test_lattice_gauss_markov_runs(setup):
    data, params0, _ = setup
    spec = LatticeSpec(policies=("pofl",), seeds=(0, 1000), n_rounds=4)
    recs = run_lattice(
        _loss_fn, data, params0, spec,
        base_cfg=POFLConfig(n_devices=12, n_scheduled=4),
        scenario="gauss_markov", scenario_params={"corr": 0.95},
    )
    assert recs.e_com.shape == (1, 1, 1, 1, 2, 4)
    assert np.isfinite(recs.e_com).all()
    assert recs.acc.shape[-1] == 0  # no eval_fn → empty eval axis


# --------------------------------------------------------------------------
# engine cache + retrace guard
# --------------------------------------------------------------------------


def test_engine_cache_no_retrace_on_repeat_call(setup):
    """A repeat ``run_pofl`` with the same config (any seed) must reuse the
    cached engine with ZERO new scan traces — the PR-2 cold-call fix and the
    CI retrace guard (``-k no_retrace``)."""
    data, params0, _ = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, policy="pofl", seed=7)
    p1, _ = run_pofl(_loss_fn, params0, data, cfg, 6)

    engine = cached_engine(_loss_fn, data, cfg)  # must be a hit, not a build
    traces_after_first = engine.n_traces
    assert traces_after_first >= 1

    stats0 = engine_cache_stats()
    p2, _ = run_pofl(_loss_fn, params0, data, cfg, 6)
    # same engine object, zero new traces, pure cache hit
    assert cached_engine(_loss_fn, data, cfg) is engine
    assert engine.n_traces == traces_after_first
    assert engine_cache_stats()["hits"] > stats0["hits"]
    assert engine_cache_stats()["misses"] == stats0["misses"]
    np.testing.assert_array_equal(np.asarray(p1["w"]), np.asarray(p2["w"]))

    # a different seed shares the engine (cfg-minus-seed keying)…
    run_pofl(_loss_fn, params0, data, dataclasses.replace(cfg, seed=123), 6)
    assert engine.n_traces == traces_after_first
    # …a different backend does not
    other = cached_engine(
        _loss_fn, data, dataclasses.replace(cfg, backend="pallas_fused")
    )
    assert other is not engine


def test_static_length_scan_pads_without_perturbing(setup):
    """n_rounds that don't divide evenly into eval segments exercise the
    active-mask padding: history lengths and trajectories must match an
    unpadded single-segment run of the same rounds."""
    data, params0, ev = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, seed=5)
    engine = SimEngine(_loss_fn, data, cfg)
    # segments [1, 3, 3] (L=3, first padded) vs one unpadded 7-round segment
    p_eval, hist = engine.run_with_history(params0, 7, eval_fn=ev, eval_every=3)
    p_plain, hist_plain = engine.run_with_history(params0, 7, eval_fn=None)
    np.testing.assert_array_equal(np.asarray(p_eval["w"]), np.asarray(p_plain["w"]))
    assert len(hist.e_com) == 7 == len(hist_plain.e_com)
    np.testing.assert_allclose(hist.e_com, hist_plain.e_com, rtol=1e-6)


# --------------------------------------------------------------------------
# aggregation backends
# --------------------------------------------------------------------------


def test_backend_parity_on_small_lattice(setup):
    """pallas_fused (fused Eq. 5→8, jnp oracle on CPU) must track the exact
    jnp physical path round-for-round on a small lattice."""
    data, params0, ev = setup
    spec = LatticeSpec(policies=("pofl",), seeds=(0, 1000), n_rounds=5)
    base = POFLConfig(
        n_devices=12, n_scheduled=4, simulate_physical=True, backend="jnp"
    )
    recs_jnp = run_lattice(
        _loss_fn, data, params0, spec, base_cfg=base, eval_fn=ev
    )
    recs_fused = run_lattice(
        _loss_fn, data, params0, spec,
        base_cfg=dataclasses.replace(base, backend="pallas_fused"), eval_fn=ev,
    )
    np.testing.assert_allclose(
        recs_fused.grad_norm, recs_jnp.grad_norm, rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(recs_fused.e_com, recs_jnp.e_com, rtol=1e-5)
    np.testing.assert_allclose(recs_fused.acc, recs_jnp.acc, rtol=1e-4, atol=1e-4)


def test_backend_interpret_mode_parity():
    """The CPU interpreter-mode path of the fused backend (the round body's
    actual Pallas kernel, interpreted) matches the jnp reference stage."""
    from repro.core import aggregation_stage

    cfg = POFLConfig(
        n_devices=6, n_scheduled=3, backend="pallas_fused",
        simulate_physical=True,
    )
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    g = jax.random.normal(ks[0], (6, 700))
    h = (jax.random.normal(ks[1], (6,)) + 1j * jax.random.normal(ks[2], (6,))).astype(
        jnp.complex64
    )
    rho = jnp.array([0.3, 0.5, 0.2, 0.0, 0.0, 0.0])
    mask = jnp.array([1.0, 1.0, 1.0, 0.0, 0.0, 0.0])
    y_interp, e_interp = aggregation_stage(
        cfg, g, rho, h, mask, ks[3], 1e-8, use_pallas="interpret"
    )
    y_ref, e_ref = aggregation_stage(
        cfg, g, rho, h, mask, ks[3], 1e-8, use_pallas=False
    )
    cfg_jnp = dataclasses.replace(cfg, backend="jnp")
    y_jnp, e_jnp = aggregation_stage(cfg_jnp, g, rho, h, mask, ks[3], 1e-8)
    np.testing.assert_allclose(np.asarray(y_interp), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_interp), np.asarray(y_jnp), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(e_interp), float(e_jnp), rtol=1e-5)
    np.testing.assert_allclose(float(e_ref), float(e_jnp), rtol=1e-5)


def test_unknown_backend_rejected(setup):
    data, params0, _ = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, backend="nonsense")
    with pytest.raises(ValueError):
        run_pofl(_loss_fn, params0, data, cfg, 1)


def test_interpret_env_var_dispatch_and_cache_keying(setup, monkeypatch):
    """REPRO_PALLAS_INTERPRET flips the 'auto' dispatch to interpret mode at
    trace time, and cached_engine keys on it so a flipped var can never
    replay a stale-mode trace."""
    from repro.kernels.aircomp.ops import resolve_mode

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_mode("auto") in (True, False)  # plain hardware dispatch
    assert resolve_mode(False) is False and resolve_mode("interpret") == "interpret"
    data, _, _ = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, backend="pallas_fused")
    eng_plain = cached_engine(_loss_fn, data, cfg)

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_mode("auto") == "interpret"
    assert cached_engine(_loss_fn, data, cfg) is not eng_plain


def test_cached_engine_accepts_array_scenario_params(setup):
    """Anything SimEngine accepts as a scenario param must also key the
    cache (arrays/lists freeze to tuples instead of raising TypeError)."""
    data, _, _ = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4)
    params = {"corr": jnp.float32(0.9)}
    e1 = cached_engine(_loss_fn, data, cfg, scenario="gauss_markov",
                       scenario_params=params)
    e2 = cached_engine(_loss_fn, data, cfg, scenario="gauss_markov",
                       scenario_params={"corr": jnp.float32(0.9)})
    assert e2 is e1
    e3 = cached_engine(_loss_fn, data, cfg, scenario="gauss_markov",
                       scenario_params={"corr": jnp.float32(0.5)})
    assert e3 is not e1


def test_fused_backend_empty_rounds_finite(setup):
    """All-dropped rounds must not NaN the fused backend: its jnp oracle
    cancels the a=inf denoise scalar algebraically like the kernel does
    (the naive a·s → (…)/a composition produced 0·inf)."""
    data, params0, _ = setup
    cfg = POFLConfig(
        n_devices=12, n_scheduled=3, policy="pofl", seed=0,
        backend="pallas_fused",
    )
    engine = SimEngine(
        _loss_fn, data, cfg, scenario="dropout",
        scenario_params={"p_drop": 0.85},
    )
    state = engine.init(params0, 0)
    final, recs = jax.jit(
        lambda s: engine.scan_rounds(
            s, jnp.arange(50, dtype=jnp.int32), jnp.zeros(50, bool)
        )
    )(state)
    assert (np.asarray(recs.n_scheduled) == 0).any()  # empty rounds occurred
    assert np.isfinite(np.asarray(final.params["w"])).all()
    assert np.isfinite(np.asarray(recs.grad_norm)).all()


# --------------------------------------------------------------------------
# heterogeneous (Dirichlet-sized) shards
# --------------------------------------------------------------------------


def test_dirichlet_sizes_apportionment():
    sizes = dirichlet_sizes(1000, 8, beta=0.3, min_per_device=2, seed=0)
    assert sizes.sum() == 1000 and (sizes >= 2).all()
    near_equal = dirichlet_sizes(1000, 8, beta=1e6, seed=0)
    assert near_equal.max() - near_equal.min() <= 2  # β→∞ ⇒ ~equal shards
    with pytest.raises(ValueError):
        dirichlet_sizes(10, 8, min_per_device=2)


def test_hetero_lattice_end_to_end(setup):
    """Acceptance: a lattice sweep with Dirichlet-sized (unequal) shards runs
    end to end through engine + lattice, weights following the true m_i/M."""
    _, params0, ev = setup
    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 1200, key)
    data = partition_dirichlet_sized(x, y, n_devices=12, beta=0.4, seed=0)
    frac = np.asarray(data.data_frac)
    assert frac.sum() == pytest.approx(1.0, rel=1e-6)
    assert frac.std() > 0.01  # genuinely non-uniform

    spec = LatticeSpec(
        policies=("pofl", "importance"), seeds=(0, 1000), n_rounds=6,
        eval_every=3,
    )
    recs = run_lattice(
        _loss_fn, data, params0, spec,
        base_cfg=POFLConfig(n_devices=12, n_scheduled=4), eval_fn=ev,
    )
    assert recs.e_com.shape == (1, 2, 1, 1, 2, 6)
    assert np.isfinite(recs.e_com).all() and np.isfinite(recs.acc).all()
    assert (recs.n_scheduled >= 1).all()

    # and through the run_pofl wrapper (engine path) as well
    cfg = POFLConfig(n_devices=12, n_scheduled=4, seed=0)
    params, hist = run_pofl(_loss_fn, params0, data, cfg, 5, eval_fn=ev, eval_every=2)
    assert np.isfinite(np.asarray(params["w"])).all()
    assert hist.test_acc[-1] > 0.2  # it actually learns a bit in 5 rounds


def test_hetero_padding_never_sampled():
    """Padded rows carry NaN features here: any draw past a device's valid
    prefix would poison the gradients, so finiteness proves the sampler
    respects n_samples."""
    from repro.core import local_gradient_stage

    n_dev, m_max, d = 4, 10, 8
    feats = np.random.default_rng(0).normal(size=(n_dev, m_max, d)).astype(np.float32)
    labels = np.random.default_rng(1).integers(0, 3, size=(n_dev, m_max))
    n_samples = np.array([10, 3, 7, 1], np.int32)
    for i, ns in enumerate(n_samples):
        feats[i, ns:] = np.nan  # poison the padding
    data = DeviceData(
        features=jnp.asarray(feats), labels=jnp.asarray(labels),
        n_samples=n_samples,
    )

    def loss(params, x, y):
        logits = x @ params["w"]
        return -jnp.mean(
            jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], axis=1)
        )

    cfg = POFLConfig(n_devices=n_dev, batch_size=6)
    for seed in range(5):
        g = local_gradient_stage(
            loss, data, cfg, {"w": jnp.zeros((d, 3))}, jax.random.PRNGKey(seed)
        )
        assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_allclose(
        np.asarray(data.data_frac), n_samples / n_samples.sum(), rtol=1e-6
    )

    # empty devices are rejected at trace time, not silently wrapped onto
    # the last padded row
    empty = DeviceData(
        features=jnp.asarray(feats), labels=jnp.asarray(labels),
        n_samples=np.array([10, 0, 7, 1], np.int32),
    )
    with pytest.raises(ValueError, match="n_samples"):
        local_gradient_stage(
            loss, empty, cfg, {"w": jnp.zeros((d, 3))}, jax.random.PRNGKey(0)
        )


def test_dirichlet_mixed_pins_sizes_and_label_histograms():
    """dirichlet_mixed = dirichlet × dirichlet_sized in one preset: for a
    fixed seed both the shard sizes and the per-device label histograms are
    pinned, both skews are genuinely present, and every sample is used
    exactly once across the valid prefixes."""
    from repro.sim import make_partition

    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 2000, key)
    dd = make_partition(
        "dirichlet_mixed", x, y, n_devices=10, beta=0.3, beta_size=0.4, seed=0
    )
    # pinned shard sizes (Dir(0.4)·2000, largest-remainder, min 1/device)
    np.testing.assert_array_equal(
        dd.n_samples, [153, 1, 365, 135, 102, 484, 234, 502, 23, 1]
    )
    assert dd.features.shape == (10, 502, 784)
    np.testing.assert_allclose(
        np.asarray(dd.data_frac), np.asarray(dd.n_samples) / 2000.0, rtol=1e-6
    )
    # pinned device-0 label histogram (Dir(0.3) label proportions)
    hist0 = np.bincount(np.asarray(dd.labels[0][:153]), minlength=10)
    np.testing.assert_array_equal(hist0, [0, 3, 14, 89, 3, 6, 0, 0, 33, 5])

    # both skews present: sizes far from equal, labels far from uniform
    sizes = np.asarray(dd.n_samples)
    assert sizes.max() > 2 * sizes.min() and sizes.sum() == 2000
    top_fracs = []
    for d in range(10):
        lab = np.asarray(dd.labels[d][: sizes[d]])
        counts = np.bincount(lab, minlength=10)
        top_fracs.append(counts.max() / counts.sum())
    assert np.mean(top_fracs) > 0.35  # vs ≈0.1–0.2 for uniform labels

    # every sample used exactly once across valid prefixes (wrap-padding
    # reuses only a device's own rows, past its n_samples prefix)
    valid = np.concatenate(
        [np.asarray(dd.features[d][: sizes[d]]) for d in range(10)]
    )
    assert np.unique(valid, axis=0).shape[0] == 2000
    part_classes, part_counts = np.unique(
        np.concatenate([np.asarray(dd.labels[d][: sizes[d]]) for d in range(10)]),
        return_counts=True,
    )
    global_classes, global_counts = np.unique(np.asarray(y), return_counts=True)
    np.testing.assert_array_equal(part_classes, global_classes)
    np.testing.assert_array_equal(part_counts, global_counts)


@pytest.mark.parametrize(
    "scenario,params",
    [("dropout", {"p_drop": 0.5}), ("churn", {"p_depart": 0.3, "p_arrive": 0.2})],
)
def test_hetero_shards_under_availability_stay_finite(setup, scenario, params):
    """Dirichlet-sized (unequal m_i/M) shards composed with availability
    scenarios: trajectory, metrics and realized |S| stay finite/clamped —
    the engine-level counterpart of the scheduling-level property test
    (tests/test_scheduling.py::test_property_unbiased_and_finite_under_availability)."""
    _, params0, _ = setup
    key = jax.random.PRNGKey(0)
    x, y = make_classification_dataset("mnist_like", 1200, key)
    data = partition_dirichlet_sized(x, y, n_devices=12, beta=0.4, seed=0)
    cfg = POFLConfig(n_devices=12, n_scheduled=4, policy="pofl", seed=0)
    engine = SimEngine(
        _loss_fn, data, cfg, scenario=scenario, scenario_params=params
    )
    state = engine.init(params0, 0)
    final, recs = jax.jit(
        lambda s: engine.scan_rounds(
            s, jnp.arange(30, dtype=jnp.int32), jnp.zeros(30, bool)
        )
    )(state)
    assert np.isfinite(np.asarray(final.params["w"])).all()
    assert np.isfinite(np.asarray(recs.e_com)).all()
    assert np.isfinite(np.asarray(recs.e_var)).all()
    n_sched = np.asarray(recs.n_scheduled)
    assert (n_sched <= 4).all() and n_sched.min() < 4  # clamping fired


# --------------------------------------------------------------------------
# churn scenario
# --------------------------------------------------------------------------


def test_churn_availability_trends_not_flickers():
    """Churn availability is a sticky Markov chain: stationary rate
    p_arrive/(p_arrive+p_depart) and lag-1 autocorr ≈ 1-p_arrive-p_depart
    (≫ 0, unlike dropout's i.i.d. flicker at autocorr 0)."""
    cfg = ChannelConfig(n_devices=24)
    p_dep, p_arr = 0.1, 0.3
    proc = make_channel_process("churn", cfg, p_depart=p_dep, p_arrive=p_arr)
    _, avails = _rollout(proc, jax.random.PRNGKey(2), 3000)
    av = np.asarray(avails)  # (T, N)
    assert set(np.unique(av)) <= {0.0, 1.0}

    stationary = p_arr / (p_arr + p_dep)
    np.testing.assert_allclose(av.mean(), stationary, atol=0.04)

    centered = av - av.mean(axis=0)
    autocorr = float(
        (centered[1:] * centered[:-1]).mean() / (centered**2).mean()
    )
    np.testing.assert_allclose(autocorr, 1.0 - p_arr - p_dep, atol=0.08)
    # devices genuinely stay offline for multi-round stretches
    run_lengths = []
    for dev in range(av.shape[1]):
        off = av[:, dev] == 0
        if off.any():
            edges = np.flatnonzero(np.diff(np.concatenate([[0], off, [0]])))
            run_lengths.extend((edges[1::2] - edges[::2]).tolist())
    assert np.mean(run_lengths) > 2.0  # E[offline sojourn] = 1/p_arrive ≈ 3.3


def test_churn_base_channel_untouched():
    """The fading trajectory under churn matches the base process exactly
    (churn only gates availability)."""
    cfg = ChannelConfig(n_devices=8)
    proc = make_channel_process("churn", cfg, base="gauss_markov", corr=0.9)
    base = make_channel_process("gauss_markov", cfg, corr=0.9)
    st_c = proc.init(jax.random.PRNGKey(4))
    # churn splits its init key: base state comes from split(key)[0]
    k_base, _ = jax.random.split(jax.random.PRNGKey(4))
    st_b = base.init(k_base)
    k = jax.random.PRNGKey(9)
    _, h_c, _ = proc.step(st_c, k)
    _, h_b, _ = base.step(st_b, jax.random.split(k)[0])
    np.testing.assert_array_equal(np.asarray(h_c), np.asarray(h_b))


def test_churn_engine_runs_finite(setup):
    data, params0, _ = setup
    cfg = POFLConfig(n_devices=12, n_scheduled=4, policy="pofl", seed=0)
    engine = SimEngine(
        _loss_fn, data, cfg, scenario="churn",
        scenario_params={"p_depart": 0.3, "p_arrive": 0.2},
    )
    state = engine.init(params0, 0)
    final, recs = jax.jit(
        lambda s: engine.scan_rounds(
            s, jnp.arange(30, dtype=jnp.int32), jnp.zeros(30, bool)
        )
    )(state)
    assert np.isfinite(np.asarray(final.params["w"])).all()
    assert np.isfinite(np.asarray(recs.e_com)).all()
    n_sched = np.asarray(recs.n_scheduled)
    assert (n_sched <= 4).all() and n_sched.min() < 4  # clamping fired


def test_churn_dirichlet_mixed_golden_trajectory():
    """Seed-pinned golden trajectory for churn availability × dirichlet_mixed
    shards — the one PR-2/PR-3 feature pair that previously had no
    end-to-end pin (churn was pinned on equal shards, dirichlet_mixed only at
    the partition level). Any change to the PRNG key discipline, the Markov
    availability chain, the mixed-partition apportionment, or the Eq. 34-37
    weighting of unequal m_i/M moves these numbers and must be deliberate.

    The pinned ``n_scheduled`` run (2, 1, 4, 3, 4, 4) doubles as a structural
    check: churn genuinely clamps |S^t| below n_scheduled=4 on early rounds.
    """
    key = jax.random.PRNGKey(3)
    x, y = make_classification_dataset("mnist_like", 600, key)
    data = partition_dirichlet_mixed(
        x, y, n_devices=10, beta=0.3, beta_size=0.4, seed=0
    )
    params0 = {"w": jnp.zeros((784, 10)), "b": jnp.zeros((10,))}
    spec = LatticeSpec(
        policies=("pofl",), noise_powers=(1e-11,), alphas=(0.1,), seeds=(0,),
        n_rounds=6,
    )
    recs = run_lattice(
        _loss_fn, data, params0, spec,
        base_cfg=POFLConfig(n_devices=10, n_scheduled=4),
        scenario="churn",
        scenario_params={"p_depart": 0.3, "p_arrive": 0.2},
    )
    cell = {f: np.asarray(getattr(recs, f)[0, 0, 0, 0, 0]) for f in
            ("e_com", "e_var", "grad_norm", "n_scheduled")}
    np.testing.assert_array_equal(
        cell["n_scheduled"], [2.0, 1.0, 4.0, 3.0, 4.0, 4.0]
    )
    golden = {
        "e_com": [0.031349364668130875, 0.001395408296957612,
                  0.012313947081565857, 0.02131267450749874,
                  0.03685463219881058, 0.007252929266542196],
        "e_var": [0.1070418655872345, 0.12386903166770935,
                  0.07931140810251236, 0.08480053395032883,
                  0.08735901862382889, 0.15798714756965637],
        "grad_norm": [0.20976485311985016, 0.06041086092591286,
                      0.18663346767425537, 0.2160150557756424,
                      0.219487726688385, 0.11000669002532959],
    }
    for f, want in golden.items():
        np.testing.assert_allclose(cell[f], want, rtol=1e-5, err_msg=f)


# --------------------------------------------------------------------------
# trial-batched fused kernel
# --------------------------------------------------------------------------


@pytest.mark.parametrize("bt,n,d", [(1, 4, 512), (3, 12, 700), (5, 30, 1024)])
def test_aircomp_fused_batch_matches_ref(bt, n, d):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    g = jax.random.normal(ks[0], (bt, n, d))
    coeff = jax.random.uniform(ks[1], (bt, n)) * (
        jax.random.uniform(ks[2], (bt, n)) > 0.3
    )
    z = jax.random.normal(ks[3], (bt, d))
    m_g = 0.1 * jax.random.normal(ks[4], (bt,))
    v_g = jax.random.uniform(ks[5], (bt,)) + 0.2
    a = jnp.linspace(1.0, 3.0, bt)

    got = aircomp_fused_batch(g, coeff, m_g, v_g, a, z, interpret=True)
    want = aircomp_fused_batch_ref(g, coeff, m_g, v_g, a, z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
