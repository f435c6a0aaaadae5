"""Plain reference of the first rounds of a PO-FL lattice cell.

Written from the paper (arXiv:2305.16854, Algorithm 1, Eqs. 5-8, 15, 34-37)
and imports nothing of the program. Each cell is one federated training
run: every round it draws a fading channel and one mini-batch per device,
computes the N local gradients, the uploaded statistics (M_i, V_i,
||g_i||), the scheduling probabilities of the cell's policy, draws |S|
devices without replacement (Eq. 36), weights them (Eq. 37), aggregates
over the air with receiver noise (Eqs. 5-8), and steps the weights. It
returns the same records the lattice does for those rounds.

What it shares with the program is the specification, not code:

* the random draws. The program documents its key discipline (one
  ``PRNGKey(seed)``, split once for the channel's distances, then per
  round ``k_batch, k_chan, k_sched, k_noise``), and the reference draws
  with the same ``jax.random`` calls, so both see the same channels,
  mini-batches, Gumbel variates and noise;
* the flat parameter order of the aggregate, which decides which noise
  sample lands on which weight: layers in sorted name order, bias before
  weight, each raveled row-major.

Arithmetic runs in float32 at ``Precision.HIGHEST``, so the reference is
the exact product of what the configuration states. ``dtype=bfloat16`` is
the control: the same computation one precision below.

A draw whose two best Gumbel-perturbed log-probabilities lie closer than
rounding can separate is decided by rounding, not by the algorithm; the
reference reports each cell's smallest such margin so that the comparison
can leave those cells out (see ``perfbench/check.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-30  # the floor every division by a channel gain or probability uses

# policy names the reference implements (Sec. IV and the Sec. V baselines)
POLICIES = ("pofl", "importance", "channel", "noisefree", "deterministic")

FAULTS = (None, "frozen", "half_batch")

BLOCK = 128  # cells per reference call, which bounds the reference's memory


def layout(shapes: dict, prefix: str = "") -> list[tuple[str, tuple]]:
    """(name, shape) of every weight in flat order: sorted names."""
    out = []
    for name in sorted(shapes):
        s = shapes[name]
        if isinstance(s, dict):
            out += layout(s, f"{prefix}{name}.")
        else:
            out.append((f"{prefix}{name}", tuple(s)))
    return out


def unflatten(flat, lay):
    out, i = {}, 0
    for name, shape in lay:
        n = int(np.prod(shape))
        out[name] = flat[i:i + n].reshape(shape)
        i += n
    return out


def flatten(params: dict, lay) -> jnp.ndarray:
    """Nested dict of weights -> the flat vector, in :func:`layout` order."""
    leaves = []
    for name, _ in lay:
        v = params
        for part in name.split("."):
            v = v[part]
        leaves.append(jnp.ravel(v))
    return jnp.concatenate(leaves)


def make_logits(config: dict, lay):
    """``logits(w_flat, x)`` of the configuration's model."""
    if config["task"] == "logreg":
        def logits(w, x):
            p = unflatten(w, lay)
            x = x.reshape(x.shape[0], -1)
            return jnp.dot(x, p["w"], precision=HIGHEST) + p["b"]
        return logits

    n_conv = len(config["conv_channels"])
    pool_after = {1, 2}  # 2x2 max pools after the second and third convs

    def logits(w, x):
        p = unflatten(w, lay)
        for i in range(n_conv):
            x = jax.lax.conv_general_dilated(
                x, p[f"conv{i}.w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
            )
            x = jax.nn.relu(x + p[f"conv{i}.b"])
            if i in pool_after:
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max,
                    (1, 2, 2, 1), (1, 2, 2, 1), "VALID",
                )
        x = jnp.mean(x, axis=(1, 2))
        x = jax.nn.relu(jnp.dot(x, p["fc1.w"], precision=HIGHEST) + p["fc1.b"])
        return jnp.dot(x, p["out.w"], precision=HIGHEST) + p["out.b"]
    return logits


def nll(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


class Reference:
    """The first ``n_rounds`` rounds of lattice cells, vmapped over cells.

    ``train_x`` ``(N, m, ...)``, ``train_y`` ``(N, m)``, ``test_x``,
    ``test_y`` and the initial weights come from the benchmark
    (``perfbench/data.py``), never from the program.
    """

    def __init__(self, config: dict, train_x, train_y, test_x, test_y,
                 n_rounds: int = 3, dtype=jnp.float32, fault=None):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.config = config
        self.n_rounds = n_rounds
        self.dtype = dtype
        self.fault = fault
        from perfbench.data import param_shapes  # the layout's shapes

        self.lay = layout(param_shapes(config))
        self.dim = sum(int(np.prod(s)) for _, s in self.lay)
        if self.dim != config["dim"]:
            raise ValueError(f"layout has D = {self.dim}, config says {config['dim']}")
        self.logits = make_logits(config, self.lay)
        # the dataset is an argument of the jitted program, not a constant
        # baked into it, so the compiled reference stays small and cached
        self.data = (jnp.asarray(train_x, dtype), jnp.asarray(train_y),
                     jnp.asarray(test_x, dtype), jnp.asarray(test_y))
        self._cells = jax.jit(jax.vmap(self._cell, in_axes=(None, None, 0, 0, 0, 0)))

    # -- one cell -----------------------------------------------------------

    def _channel_gains(self, key):
        ch = self.config["channel"]
        d = jax.random.uniform(
            key, (self.config["n_devices"],), minval=ch["d_min"], maxval=ch["d_max"]
        )
        return ch["antenna_gain"] * (
            3.0e8 / (4.0 * jnp.pi * ch["carrier_freq"] * d)
        ) ** ch["path_loss_exp"]

    def _fading(self, gains, key):
        """|h_i| of Rayleigh block fading h_i = sqrt(g_i) CN(0, 1)."""
        k_re, k_im = jax.random.split(key)
        re = jax.random.normal(k_re, gains.shape)
        im = jax.random.normal(k_im, gains.shape)
        lam = (re + 1j * im) / jnp.sqrt(2.0)
        return jnp.abs(jnp.sqrt(gains).astype(jnp.complex64) * lam.astype(jnp.complex64))

    def _grads(self, w, key, train_x, train_y):
        """Per-device mini-batch gradients -> (N, D)."""
        c = self.config
        n, m = train_y.shape
        idx = jax.random.randint(key, (n, c["batch_size"]), 0, m)
        if self.fault == "half_batch":
            idx = idx[:, : c["batch_size"] // 2]
        xb = jax.vmap(lambda x, i: x[i])(train_x, idx)
        yb = jnp.take_along_axis(train_y, idx, axis=1)
        loss = lambda w_, x, y: nll(self.logits(w_, x), y)  # noqa: E731
        return jax.vmap(jax.grad(loss), in_axes=(None, 0, 0))(w, xb, yb)

    def _probs(self, policy, norms, vars_, h_abs, frac, alpha, sigma2):
        """Single-draw probabilities of each policy; ``policy`` selects."""
        c = self.config
        p_tx = c["tx_power"]

        def q_pofl(s2):  # Eq. 35
            v_tilde = jnp.sum(frac * vars_)
            com = (1.0 + alpha) * v_tilde * self.dim * s2 * frac**2
            com = com / jnp.maximum(p_tx * h_abs**2, EPS)
            var = (1.0 + 1.0 / alpha) * frac**2 * norms**2
            return jnp.sqrt(com + var)

        qs = jnp.stack([
            q_pofl(sigma2),            # pofl
            frac * norms,              # importance
            h_abs**2,                  # channel
            q_pofl(0.0),               # noisefree
            jnp.ones_like(h_abs),      # deterministic
        ]).astype(jnp.float32)
        q = jnp.maximum(qs[policy], EPS)
        return q / jnp.sum(q)

    def _draw(self, key, probs):
        """Eq. 36: |S| sequential draws without replacement (Gumbel-max).
        Returns (mask, per-draw indices, per-draw renormalized probs, the
        smallest gap between the two best perturbed log-probs)."""
        n, s = probs.shape[0], self.config["n_scheduled"]
        mask = jnp.zeros(n)
        cum = jnp.zeros(())
        idxs, qks, margin = [], [], jnp.inf
        for k_key in jax.random.split(key, s):
            free = (mask == 0) & (probs > 0)
            logits = jnp.where(free, jnp.log(jnp.maximum(probs, EPS)), -jnp.inf)
            pert = jax.random.gumbel(k_key, (n,), jnp.float32) + logits
            i = jnp.argmax(pert)
            second = jnp.max(jnp.where(jnp.arange(n) == i, -jnp.inf, pert))
            margin = jnp.minimum(margin, pert[i] - second)
            qks.append(probs[i] / jnp.maximum(1.0 - cum, EPS))
            idxs.append(i)
            mask = mask.at[i].set(1.0)
            cum = cum + probs[i]
        return mask, jnp.stack(idxs), jnp.stack(qks), margin

    def _cell(self, data, w0, seed, noise_power, alpha, policy):
        c, dt = self.config, self.dtype
        train_x, train_y, test_x, test_y = data
        n = c["n_devices"]
        frac = jnp.full((n,), 1.0 / n)
        key = jax.random.PRNGKey(seed)
        k_chan_init, key = jax.random.split(key)
        gains = self._channel_gains(k_chan_init)
        w = w0.astype(dt)
        agg_sigma2 = jnp.where(policy == POLICIES.index("noisefree"), 0.0, noise_power)
        recs = {f: [] for f in ("e_com", "e_var", "grad_norm", "n_scheduled")}
        margin = jnp.inf
        ev = None
        for t in range(self.n_rounds):
            key, k_round = jax.random.split(key)
            k_batch, k_chan, k_sched, k_noise = jax.random.split(k_round, 4)
            h_abs = self._fading(gains, k_chan)
            g = self._grads(w, k_batch, train_x, train_y).astype(dt)                    # (N, D)
            mean = jnp.mean(g, axis=-1)                               # M_i
            var = jnp.mean((g - mean[:, None]) ** 2, axis=-1)         # V_i
            norms = jnp.sqrt(jnp.sum(g * g, axis=-1))                 # ||g_i||
            probs = self._probs(policy, norms, var, h_abs, frac, alpha, noise_power)
            mask, idx, qk, m = self._draw(k_sched, probs)
            margin = jnp.minimum(margin, m)
            # Eq. 37 weights; the deterministic baseline aggregates directly
            w_k = frac[idx] / jnp.maximum(qk, EPS) / c["n_scheduled"]
            rho_po = jnp.zeros(n).at[idx].add(w_k)
            rho_det = mask * frac / jnp.maximum(jnp.sum(mask * frac), EPS)
            rho = jnp.where(policy == POLICIES.index("deterministic"), rho_det, rho_po)
            rho = rho * mask
            # Eqs. 5-8 under Lemma 1's transceivers: b_i h_i = rho_i a
            m_g = jnp.sum(rho * mean)
            v_g = jnp.sum(rho * var)
            a = jnp.min(jnp.where(
                mask > 0, jnp.sqrt(c["tx_power"]) * h_abs / jnp.maximum(rho, EPS), jnp.inf
            ))
            z = (jax.random.normal(k_noise, (self.dim,)) * jnp.sqrt(agg_sigma2)).astype(dt)
            sqrt_vg = jnp.sqrt(jnp.maximum(v_g, EPS)).astype(dt)
            y = (jnp.sum(rho[:, None].astype(dt) * g, axis=0)
                 + ((1.0 - jnp.sum(rho)) * m_g).astype(dt)
                 + (sqrt_vg / a.astype(dt)) * z)
            # Eq. 15 and Thm. 1's global update variance
            ratio = jnp.where(mask > 0, (rho / jnp.maximum(h_abs, EPS)) ** 2, 0.0)
            e_com = self.dim * agg_sigma2 * v_g / c["tx_power"] * jnp.max(ratio)
            est = jnp.sum(rho[:, None].astype(dt) * g, axis=0)
            target = jnp.sum(frac[:, None].astype(dt) * g, axis=0)
            e_var = jnp.sum((est - target) ** 2)
            recs["e_com"].append(e_com)
            recs["e_var"].append(e_var)
            recs["grad_norm"].append(jnp.sqrt(jnp.sum(y * y)))
            recs["n_scheduled"].append(jnp.sum(mask))
            lr = jnp.maximum(c["lr0"] * c["lr_decay"] ** jnp.float32(t), c["lr_min"])
            if self.fault != "frozen":
                w = (w - lr.astype(dt) * y).astype(dt)
            if t == 0:  # the lattice evaluates after round 0's update
                lg = self.logits(w, test_x)
                n_correct = jnp.sum(jnp.argmax(lg, axis=-1) == test_y)
                ev = {
                    "loss": nll(lg, test_y),
                    "acc": n_correct / test_y.shape[0],
                }
        out = {f: jnp.stack(v).astype(jnp.float32) for f, v in recs.items()}
        out.update({f"eval0_{k}": v.astype(jnp.float32) for k, v in ev.items()})
        out["margin"] = margin
        return out

    # -- many cells ---------------------------------------------------------

    def run(self, w0_flat, seeds, noise_powers, alphas, policies) -> dict:
        """Records of every cell, computed :data:`BLOCK` cells at a time (the
        last block is padded with repeats, so one program serves all)."""
        seeds = np.asarray(seeds, np.int32)
        n = seeds.shape[0]
        cols = [seeds, np.asarray(noise_powers, np.float32),
                np.asarray(alphas, np.float32), np.asarray(policies, np.int32)]
        block = min(BLOCK, n)
        outs = []
        for start in range(0, n, block):
            part = [c_[start:start + block] for c_ in cols]
            short = block - part[0].shape[0]
            if short:
                part = [np.concatenate([p, np.repeat(p[-1:], short)]) for p in part]
            res = jax.device_get(self._cells(self.data, w0_flat, *map(jnp.asarray, part)))
            outs.append({k: v[: block - short] for k, v in res.items()})
        return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}
