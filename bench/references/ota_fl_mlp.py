"""Plain float32 reference of the OTA-FL round of arXiv:2308.09082 on the
paper's 784-64-64-10 classifier: local gradient, normalisation (eq. 12),
Algorithm 1 amplification (re-solved each round under block fading),
superposition with seeded receiver noise (eq. 10) and the server step
(eq. 11), Case I schedule eta_t = 1 / t^p.

Written from the paper and the configuration file alone: it imports nothing
of the program.  It makes its own data, split, initial weights, batch draws,
channel draws and noise from the two seeds the benchmark derives, following
the simulator's documented key schedule, and solves Problem 3 exactly in
float64 (water-filling in u_k = h_k b_k, closed form per segment).

``precision`` is ``"highest"`` (float32 matmuls) or ``"high"`` (three bf16
passes, the control).  ``fault`` plants one of the faults the comparison
must catch: ``"half_batch"`` (each client's gradient over half its batch)
or ``"negate_leaf"`` (the aggregated update of the output bias negated).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12
BLOCK_CLIENTS = 128
PRECISIONS = ("highest", "high")
FAULTS = ("half_batch", "negate_leaf")
# the configuration's ``spec`` fields this reference implements: a value it
# must hold, or None for any value it reads
SPEC = {"dataset": "synthetic_mnist", "split": "dirichlet", "model.kind": "mlp",
        "scheme": "normalized", "case": "I", "amplification": "optimal",
        "local_steps": 1, "participation": 1.0, "server_opt": "sgd",
        "num_train": None, "num_test": None, "alpha": None, "batch_size": None,
        "model.hidden": None, "num_devices": None, "p": None,
        "smoothness_L": None, "expected_loss_drop": None, "channel_mean": None,
        "noise_var": None, "b_max": None, "block_fading": None,
        # the program's backend, its blocking of the devices and its
        # sharding of the blocks change only the order of its sums; with
        # every device taking part, the participation draw and the
        # active-set gather select all of them
        "backend": None, "k_block": None, "device_mesh": None,
        "participation_mode": None, "active_gather": None}


def check_spec(spec: dict) -> None:
    """Refuse a configuration this reference does not implement."""
    for key, value in spec.items():
        if key not in SPEC:
            raise ValueError(f"the reference does not implement {key!r}")
        if SPEC[key] is not None and value != SPEC[key]:
            raise ValueError(f"the reference implements {key}={SPEC[key]!r}, "
                             f"not {value!r}")


# ---------------------------------------------------------------- inputs


def synthetic_mnist(key, num_examples: int, num_classes: int = 10,
                    side: int = 28, noise: float = 0.35):
    """Class templates (Gaussian, twice box-smoothed with edge padding) plus
    per-example Gaussian noise; labels uniform."""
    k_tmpl, k_lab, k_noise = jax.random.split(key, 3)
    tmpl = jax.random.normal(k_tmpl, (num_classes, side * side))
    tmpl = tmpl.reshape(num_classes, side, side)
    for _ in range(2):
        pad = jnp.pad(tmpl, ((0, 0), (1, 1), (1, 1)), mode="edge")
        tmpl = sum(pad[:, i:i + side, j:j + side]
                   for i in range(3) for j in range(3)) / 9.0
    tmpl = tmpl.reshape(num_classes, side * side)
    labels = jax.random.randint(k_lab, (num_examples,), 0, num_classes)
    x = tmpl[labels] + noise * jax.random.normal(
        k_noise, (num_examples, side * side))
    return x, labels


def dirichlet_split(key, labels: np.ndarray, num_devices: int,
                    alpha: float) -> List[np.ndarray]:
    """Per class, shuffle its rows and cut them by Dirichlet(alpha)
    proportions; an empty device takes one row from the largest."""
    rng = np.random.default_rng(
        int(jax.random.randint(key, (), 0, 2 ** 31 - 1)))
    parts: List[List[int]] = [[] for _ in range(num_devices)]
    for c in np.unique(labels):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * num_devices)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for d, part in enumerate(np.split(idx, cuts)):
            parts[d].extend(part.tolist())
    for d in range(num_devices):
        if not parts[d]:
            donor = int(np.argmax([len(p) for p in parts]))
            parts[d].append(parts[donor].pop())
    return [np.sort(np.asarray(p, dtype=np.int64)) for p in parts]


def init_params(key, layers) -> Dict[str, jax.Array]:
    """Gaussian weights scaled by 1/sqrt(fan-in), zero biases."""
    keys = jax.random.split(key, len(layers) - 1)
    p = {}
    for i, (k, fan_in, fan_out) in enumerate(zip(keys, layers[:-1],
                                                  layers[1:]), start=1):
        p[f"w{i}"] = jax.random.normal(k, (fan_in, fan_out)) * (1 / math.sqrt(fan_in))
        p[f"b{i}"] = jnp.zeros((fan_out,))
    return p


def _round_indices(key, t: int, parts: List[np.ndarray], batch: int):
    """[K, batch] row indices of round t: device d draws ``batch`` positions
    in its shard from fold_in(fold_in(key, t), d), with replacement."""
    sizes = jnp.asarray([len(p) for p in parts], jnp.int32)
    base = jax.random.fold_in(key, t)
    choices = np.asarray(jax.vmap(lambda d, n: jax.random.randint(
        jax.random.fold_in(base, d), (batch,), 0, n))(
            jnp.arange(len(parts)), sizes))
    return np.stack([p[choices[d]] for d, p in enumerate(parts)])


def rayleigh(key, num_devices: int, channel_mean: float):
    """Rayleigh envelopes with mean ``channel_mean`` (float32)."""
    scale = channel_mean / math.sqrt(math.pi / 2.0)
    iq = jax.random.normal(key, (num_devices, 2))
    return scale * jnp.sqrt(jnp.sum(iq * iq, axis=-1))


def problem3(h: np.ndarray, noise_var: float, n: int, b_max: float):
    """Exact Problem 3: min (4 sum u^2 + n sigma^2) / (sum u)^2 over
    0 <= u_k <= h_k b_max.  The optimum is u_k = min(t, u_k^max); between
    sorted caps the objective in t has its stationary point at
    t = (4 D + c) / (4 A) (D, A: squares and sum of the capped caps).
    Returns (b, Z) in float64."""
    h = np.asarray(h, np.float64)
    u_max = h * b_max
    c = max(n * noise_var, 1e-12 * float(np.sum(4.0 * u_max * u_max)))
    q = np.sort(u_max)
    k = q.size
    best_t, best_f = q[-1], math.inf
    d_cap = a_cap = 0.0
    lo = 0.0
    for j in range(k):
        hi = q[j]
        m = k - j
        t = hi if a_cap <= 0.0 else min(max((4.0 * d_cap + c) / (4.0 * a_cap),
                                            lo), hi)
        f = (4.0 * (d_cap + m * t * t) + c) / (a_cap + m * t) ** 2
        if f < best_f:
            best_t, best_f = t, f
        d_cap += hi * hi
        a_cap += hi
        lo = hi
    u = np.minimum(best_t, u_max)
    b = np.where(h > 0, u / np.where(h > 0, h, 1.0), 0.0)
    return b, best_f


# ---------------------------------------------------------------- matmuls


def _split_bf16(x):
    """x = hi + lo with hi the bf16 truncation of x and lo rounded to bf16."""
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _dot3_raw(a, b):
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return d(ah, bh) + (d(ah, bl) + d(al, bh))


@jax.custom_vjp
def dot_high(a, b):
    """float32 matmul in three bf16 passes (TPU ``Precision.HIGH``), the
    same on every backend."""
    return _dot3_raw(a, b)


def _dot_high_fwd(a, b):
    return _dot3_raw(a, b), (a, b)


def _dot_high_bwd(res, g):
    a, b = res
    return _dot3_raw(g, b.T), _dot3_raw(a.T, g)


dot_high.defvjp(_dot_high_fwd, _dot_high_bwd)


def dot_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _dot(precision: str):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return dot_highest if precision == "highest" else dot_high


def loss_fn(params, x, y, precision: str = "highest"):
    """Mean softmax cross-entropy of the 3-layer classifier, one ReLU."""
    dot = _dot(precision)
    h = jax.nn.relu(dot(x, params["w1"]) + params["b1"])
    h = dot(h, params["w2"]) + params["b2"]
    logits = dot(h, params["w3"]) + params["b3"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


# ---------------------------------------------------------------- the round


def _make_round(noise_var: float, p_exp: float, precision: str,
                fault: Optional[str], kb: int):
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    grad = jax.grad(lambda p, xb, yb: loss_fn(p, xb, yb, precision))

    @jax.jit
    def round_fn(params, x, y, idx, hb, a, t, noise_key):
        k, batch = idx.shape
        if fault == "half_batch":
            idx = idx[:, :batch // 2]
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

        def block(acc, xs):
            idx_b, hb_b = xs
            g = jax.vmap(grad, in_axes=(None, 0, 0))(params, x[idx_b],
                                                     y[idx_b])
            leaves = jax.tree_util.tree_leaves(g)
            norms = jnp.sqrt(sum(jnp.sum(jnp.square(l.reshape(kb, -1)), 1)
                                 for l in leaves))
            w = hb_b / (norms + EPS)
            acc = jax.tree_util.tree_map(
                lambda s, l: s + jnp.sum(
                    w.reshape((kb,) + (1,) * (l.ndim - 1)) * l, axis=0),
                acc, g)
            return acc, norms

        nb = k // kb
        acc, norms = jax.lax.scan(
            block, zeros, (idx.reshape(nb, kb, -1), hb.reshape(nb, kb)))
        norms = norms.reshape(-1)
        flat, tree = jax.tree_util.tree_flatten(acc)
        keys = jax.random.split(noise_key, len(flat))
        std = jnp.sqrt(jnp.asarray(noise_var, jnp.float32))
        upd = [a * (s + std * jax.random.normal(kk, s.shape, jnp.float32))
               for s, kk in zip(flat, keys)]
        upd = jax.tree_util.tree_unflatten(tree, upd)
        if fault == "negate_leaf":
            upd = {**upd, "b3": -upd["b3"]}
        eta = 1.0 / jnp.maximum(t.astype(jnp.float32), 1.0) ** p_exp
        new = jax.tree_util.tree_map(lambda w_, u: w_ - eta * u, params, upd)
        diag = {"grad_norm_mean": jnp.mean(norms),
                "grad_norm_min": jnp.min(norms),
                "grad_norm_max": jnp.max(norms),
                "update_norm": jnp.sqrt(sum(jnp.sum(jnp.square(u))
                                            for u in jax.tree_util.tree_leaves(upd)))}
        return new, diag

    return round_fn


class Reference:
    """The inputs of one seed, and ``follow`` to run the first rounds."""

    def __init__(self, config: dict, data_seed: int, fl_seed: int):
        check_spec(config["spec"])
        self.cfg = c = config["spec"]
        self.fl_seed = fl_seed
        root = jax.random.PRNGKey(data_seed)
        n_tr, n_te = c["num_train"], c["num_test"]
        x, labels = synthetic_mnist(root, n_tr + n_te)
        self.x, self.y = x[:n_tr], labels[:n_tr]
        self.x_eval, self.y_eval = x[n_tr:], labels[n_tr:]
        self.parts = dirichlet_split(jax.random.fold_in(root, 1),
                                     np.asarray(labels[:n_tr]),
                                     c["num_devices"], c["alpha"])
        m = config["model"]
        if m["hidden"] != c["model.hidden"]:
            raise ValueError("model.hidden and the spec's model.hidden differ")
        self.layers = [m["in_dim"], m["hidden"], m["hidden"], m["num_classes"]]
        self.params0 = init_params(jax.random.fold_in(root, 2), self.layers)
        self.batch_key = jax.random.PRNGKey(data_seed + 3)
        self.n = sum(int(np.prod(v.shape)) for v in self.params0.values())

    def _gain(self, h64, b64):
        """Case I: S* of eq. (26) and a = 1 / (S sum h b)."""
        c = self.cfg
        _, z = problem3(h64, c["noise_var"], self.n, c["b_max"])
        s = math.sqrt(c["smoothness_L"] * (z + 1.0) * c["p"]
                      / ((2.0 * c["p"] - 1.0) * c["expected_loss_drop"]))
        return 1.0 / (s * float(np.sum(h64 * b64)))

    def follow(self, rounds: int, segment: int, traffic_spec: dict,
               precision: str = "highest", fault: Optional[str] = None):
        """Run rounds 1..``rounds`` under the traffic mix's ``spec`` fields;
        return the parameters after every ``segment`` rounds (as the program
        hands them back) and the per-round diagnostics."""
        check_spec(traffic_spec)
        block_fading = traffic_spec.get("block_fading", False)
        c = self.cfg
        k = c["num_devices"]
        kb = min(k, BLOCK_CLIENTS)
        round_fn = _make_round(c["noise_var"], c["p"], precision, fault, kb)
        h0 = np.asarray(rayleigh(jax.random.PRNGKey(self.fl_seed), k,
                                 c["channel_mean"]), np.float64)
        b0, _ = problem3(h0, c["noise_var"], self.n, c["b_max"])
        a0 = self._gain(h0, b0)
        eff_gain = np.float32(a0 * float(np.sum(h0 * b0)))
        noise_root = jax.random.PRNGKey(self.fl_seed + 1)
        chan_root = jax.random.PRNGKey(self.fl_seed + 2)
        params = self.params0
        snaps, diags = [], []
        for t in range(1, rounds + 1):
            if block_fading:
                h = rayleigh(jax.random.fold_in(chan_root, t), k,
                             c["channel_mean"])
                b, _ = problem3(np.asarray(h, np.float64), c["noise_var"],
                                self.n, c["b_max"])
                b = jnp.asarray(b, jnp.float32)
                a = (eff_gain / jnp.sum(h * b)).astype(jnp.float32)
            else:
                h = jnp.asarray(h0, jnp.float32)
                b = jnp.asarray(b0, jnp.float32)
                a = jnp.float32(a0)
            idx = jnp.asarray(_round_indices(self.batch_key, t, self.parts,
                                             c["batch_size"]), jnp.int32)
            params, diag = round_fn(params, self.x, self.y, idx, h * b, a,
                                    jnp.asarray(t, jnp.int32),
                                    jax.random.fold_in(noise_root, t))
            diags.append(diag)
            if t % segment == 0:
                snaps.append(jax.device_get(params))
        diags = jax.device_get(diags)
        return {"params0": jax.device_get(self.params0), "params": snaps,
                "diag": {key: np.asarray([d[key] for d in diags], np.float64)
                         for key in diags[0]}}

    def eval_loss(self, params) -> float:
        """Held-out loss of ``params`` at float32 matmul precision."""
        p = jax.tree_util.tree_map(jnp.asarray, params)
        return float(jax.jit(loss_fn, static_argnums=3)(
            p, self.x_eval, self.y_eval, "highest"))
