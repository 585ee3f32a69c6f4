"""Plain reference of the first rounds of a QCCF fleet experiment.

Written from the semantics the configuration file states (paper
arXiv 2402.12957, Algorithm 1 and eq. 2, 14-21, 26; the program's
documented key schedule), importing nothing of the program under test:

* data, client drop and initial weights are generated here from the seed,
  in the same way and order as the configuration's generator describes;
* the channel is the Rician / UMa rate draw of eq. 14;
* the decision is greedy channel assignment (or the genetic search over
  assignments, for the ``compiled-ga`` policy) with the per-client
  quantization level found by brute force over every integer level and
  the latency-tight CPU frequency; the program solves the same problem
  through the KKT closed form;
* local SGD, stochastic quantization and the eq.-2 aggregate are written
  out plainly, and the global model is scored on the test set.

Decision arithmetic runs in float64 on the host, the model at the
precision the configuration states (``precision``: the storage type and
the dot and convolution precision; ``default`` is one bfloat16 pass with
float32 accumulation on a TPU, full float32 on a CPU).
``precision="bfloat16"`` is the control: every quantity is rounded to
bfloat16 and the model is stored in bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops

RANGE_BITS = 32.0   # bits of the per-client range theta on the wire (eq. 5)
EMA_DECAY = 0.7     # estimator blend of the observed G^2 / sigma^2
SIGMA_FLOOR = 1e-8
GA_KEY_TAG = 11     # fold_in tag of the per-round GA key
FEAS_TOL = 1e-5     # f32 allowance at the f_max / T_max boundaries
FAULTS = ("unchanged", "half_batch", "altered")
DOT_PRECISION = {"default": jax.lax.Precision.DEFAULT,
                 "high": jax.lax.Precision.HIGH,
                 "highest": jax.lax.Precision.HIGHEST}
RATE_MATCH = 1e-2   # a reported rate within 1% of a channel's: that channel


# ------------------------------------------------------------------- data

def client_sizes(cfg: dict, seed: int) -> np.ndarray:
    """D_i ~ N(mu, beta), at least ``size_floor`` samples, per client."""
    d = cfg["data"]
    return np.maximum(np.random.default_rng(seed).normal(
        d["mu"], d["beta"], cfg["n_clients"]), d["size_floor"]).astype(np.int64)


class Workload:
    """The deployment's data, drawn from the seed: client sizes, label
    skew, per-client datasets (drawn on demand), the test set and the
    client drop."""

    def __init__(self, cfg: dict, seed: int) -> None:
        m, d = cfg["model"], cfg["data"]
        self.cfg, self.seed = cfg, int(seed)
        u, k = cfg["n_clients"], m["n_classes"]
        shape = (m["in_hw"], m["in_hw"], m["in_ch"])
        self.sample_shape = shape
        rng = np.random.default_rng(self.seed)
        self.templates = (d["template_scale"] * rng.standard_normal(
            (k,) + shape)).astype(np.float32)
        self.sizes = client_sizes(cfg, self.seed)
        self.probs = np.random.default_rng(self.seed).dirichlet(
            np.full(k, d["alpha_dirichlet"]), size=u)
        ch = cfg["channel"]
        r = ch["radius_m"] * np.sqrt(
            np.random.default_rng(self.seed).uniform(size=u))
        self.distances = np.maximum(r, ch["near_field_m"])

    def _sample(self, n: int, probs, rng) -> tuple[np.ndarray, np.ndarray]:
        k = self.templates.shape[0]
        y = rng.choice(k, size=n, p=probs)
        x = self.templates[y] + self.cfg["data"]["noise_scale"] * \
            rng.standard_normal((n,) + self.sample_shape).astype(np.float32)
        return x.astype(np.float32), y.astype(np.int32)

    def client(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng(self.seed * 1000 + int(i))
        return self._sample(int(self.sizes[i]), self.probs[i], rng)

    def test_set(self) -> tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng(self.seed + 999)
        return self._sample(self.cfg["data"]["n_test"], None, rng)


# ------------------------------------------------------------------ model

def init_params(model: dict, key) -> dict:
    """Truncated-normal weights (scale 0.1 conv, 0.05 dense), zero bias."""
    convs, hidden = model["conv_channels"], model["hidden"]
    keys = jax.random.split(key, len(convs) + len(hidden) + 1)
    tn = functools.partial(jax.random.truncated_normal, lower=-2.0, upper=2.0,
                           dtype=jnp.float32)
    p, cin, kk = {}, model["in_ch"], model["kernel"]
    for i, ch in enumerate(convs):
        p[f"conv{i}"] = {"b": jnp.zeros((ch,), jnp.float32),
                         "w": 0.1 * tn(keys[i], shape=(kk, kk, cin, ch))}
        cin = ch
    dim = flat_dim(model)
    for j, h in enumerate(hidden):
        p[f"fc{j}"] = {"b": jnp.zeros((h,), jnp.float32),
                       "w": 0.05 * tn(keys[len(convs) + j], shape=(dim, h))}
        dim = h
    p["out"] = {"b": jnp.zeros((model["n_classes"],), jnp.float32),
                "w": 0.05 * tn(keys[-1], shape=(dim, model["n_classes"]))}
    return p


def flat_dim(model: dict) -> int:
    hw = model["in_hw"] // 2 ** (len(model["conv_channels"])
                                 + int(model["extra_pool"]))
    return hw * hw * model["conv_channels"][-1]


def wire_order(params: dict) -> list[tuple[str, str]]:
    """Order of the leaves in the flat wire vector: layers by name, then
    bias before weight, each leaf row-major."""
    return [(lay, leaf) for lay in sorted(params) for leaf in sorted(params[lay])]


def flatten(params: dict) -> jax.Array:
    return jnp.concatenate([params[a][b].reshape(-1)
                            for a, b in wire_order(params)])


def unflatten(flat: jax.Array, like: dict) -> dict:
    out, at = {}, 0
    for a, b in wire_order(like):
        shape = like[a][b].shape
        n = math.prod(shape)
        out.setdefault(a, {})[b] = flat[at:at + n].reshape(shape)
        at += n
    return out


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def forward(model: dict, p: dict, x, precision):
    """Conv(SAME) + ReLU + 2x2 max-pool per conv, optional extra pool,
    ReLU dense hiddens, linear head."""
    dt = p["out"]["w"].dtype
    x = x.astype(dt)
    for i in range(len(model["conv_channels"])):
        c = p[f"conv{i}"]
        x = jax.lax.conv_general_dilated(
            x, c["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
            preferred_element_type=dt) + c["b"]
        x = _pool(jax.nn.relu(x))
    if model["extra_pool"]:
        x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    for j in range(len(model["hidden"])):
        f = p[f"fc{j}"]
        x = jax.nn.relu(jnp.dot(x, f["w"], precision=precision,
                                preferred_element_type=dt) + f["b"])
    o = p["out"]
    return jnp.dot(x, o["w"], precision=precision,
                   preferred_element_type=dt) + o["b"]


def xent(logits, y):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# --------------------------------------------------------------- decision

def _identity(x):
    return np.asarray(x, np.float64)


def _to_bf16(x):
    return np.asarray(x, np.float64).astype(jnp.bfloat16).astype(np.float64)


def draw_rates(key, dist: np.ndarray, cfg: dict, rnd) -> np.ndarray:
    """(U, C) uplink rates of one round: Rician small-scale power times
    the UMa large-scale gain, through Shannon's formula (eq. 14)."""
    ch = cfg["channel"]
    u, c = cfg["n_clients"], cfg["n_channels"]
    k, zeta = ch["rician_k"], ch["rician_zeta"]
    los = math.sqrt(k / (k + 1.0) * zeta)
    nlos = math.sqrt(zeta / (2.0 * (k + 1.0)))
    kx, ky = jax.random.split(key)
    nx = np.asarray(jax.random.normal(kx, (1, u, c)), np.float64)[0]
    ny = np.asarray(jax.random.normal(ky, (1, u, c)), np.float64)[0]
    x, y = rnd(los + nlos * nx), rnd(nlos * ny)
    small = rnd(x * x + y * y)
    pl_db = 28.0 + 22.0 * np.log10(dist) + 20.0 * math.log10(ch["carrier_ghz"])
    large = rnd(10.0 ** ((-pl_db + ch["antenna_gain_db"]) / 10.0))
    noise = 10.0 ** (ch["noise_psd_dbm"] / 10.0) * 1e-3 * ch["bandwidth"]
    snr = rnd(ch["p_tx"] * small * large[:, None] / noise)
    return rnd(ch["bandwidth"] * np.log2(1.0 + snr))


def greedy_assign(rates: np.ndarray) -> np.ndarray:
    """(C,) channel -> client: take the best free (client, channel) pair
    min(U, C) times (first index on ties)."""
    u, c = rates.shape
    masked = rates.copy()
    assign = np.full(c, -1, np.int64)
    for _ in range(min(u, c)):
        i, ch = divmod(int(np.argmax(masked)), c)
        assign[ch] = i
        masked[i, :] = -np.inf
        masked[:, ch] = -np.inf
    return assign


@dataclasses.dataclass
class Decision:
    assign: np.ndarray   # (C,) channel -> client after the feasibility drop
    a: np.ndarray        # (U,) bool
    q: np.ndarray        # (U,) int
    v: np.ndarray        # (U,) assigned rate (0 if out)
    energy: np.ndarray   # (U,)
    data_term: float
    quant_term: float


def bound_constants(sysp: dict) -> tuple[float, float]:
    eta, tau, lip = sysp["eta"], sysp["tau"], sysp["lipschitz"]
    e2 = eta**2 * lip**2
    a1 = 2.0 * e2 * (2 * tau**3 - 3 * tau**2 + tau) / (3.0 - 6.0 * e2 * tau**2)
    a2 = eta * lip * tau + e2 * (tau**2 - tau) / (1.0 - 2.0 * e2 * tau**2)
    return a1, a2


def budgets(cfg: dict, sizes: np.ndarray, z: int) -> tuple[float, float]:
    """eps1 / eps2: the bound terms of scheduling everyone at ``target_q``
    with unit G^2, sigma^2 and range."""
    sysp = cfg["system"]
    a1, a2 = bound_constants(sysp)
    d = sizes.astype(np.float64)
    w = d / d.sum()
    eps1 = 4.0 * sysp["tau"] * np.sum(1.0 - w) + a1 * np.sum(w) + a2 * np.sum(w)
    levels = 2.0 ** cfg["lyapunov"]["target_q"] - 1.0
    eps2 = sysp["lipschitz"] / 2.0 * np.sum(w * z / (4.0 * levels**2))
    return float(eps1), float(eps2)


def finish(assign, rates, d, g_n, s_n, theta, lam2, cfg, z, rnd) -> Decision:
    """Feasibility drop, per-client best integer level at its
    latency-tight frequency, the energy and the eq.-20/21 bound terms,
    for one channel assignment."""
    sp, V = cfg["system"], cfg["lyapunov"]["v_weight"]
    q_cap = cfg["lyapunov"]["q_cap"]
    u = d.shape[0]
    v = np.zeros(u)
    for ch, i in enumerate(assign):
        if i >= 0:
            v[i] = rates[i, ch]
    te, gam, alpha = sp["tau_e"], sp["gamma"], sp["alpha"]
    qmax = rnd((v * sp["t_max"] - te * gam * d * v / sp["f_max"] - z
                - RANGE_BITS) / z)
    a = (v > 0) & (qmax >= 1.0)
    dn = np.sum(d[a])
    w_round = np.where(a, d / max(dn, 1e-12), 0.0)
    q = np.zeros(u, np.int64)
    f = np.zeros(u)
    for i in np.flatnonzero(a):
        qs = np.arange(1, q_cap + 1, dtype=np.float64)
        bits = z * qs + z + RANGE_BITS
        slack = rnd(v[i] * sp["t_max"] - bits)
        with np.errstate(divide="ignore", invalid="ignore"):
            fq = rnd(np.maximum(sp["f_min"],
                                te * gam * d[i] * v[i] / np.where(
                                    slack > 0, slack, np.nan)))
            ok = (slack > 0) & (fq <= sp["f_max"] * (1.0 + FEAS_TOL))
            fq = np.minimum(fq, sp["f_max"])
            lat = rnd(te * gam * d[i] / fq + bits / v[i])
        ok &= lat <= sp["t_max"] * (1.0 + FEAS_TOL)
        j = rnd(lam2 * w_round[i] * z * sp["lipschitz"] * theta[i] ** 2
                / (8.0 * (2.0 ** qs - 1.0) ** 2)
                + V * te * alpha * gam * d[i] * fq**2
                + sp["p_tx"] * V * z * qs / v[i])
        j = np.where(ok, j, np.inf)
        if not np.isfinite(j).any():
            a[i] = False
            continue
        best = int(np.argmin(j))           # ties keep the lower level
        q[i], f[i] = best + 1, fq[best]
    bits = np.where(a, z * q + z + RANGE_BITS, 0.0)
    energy = rnd(np.where(a, te * alpha * gam * d * f**2
                          + sp["p_tx"] * bits / np.where(a, v, 1.0), 0.0))
    a1, a2 = bound_constants(sp)
    w_full = d / d.sum()
    af = a.astype(np.float64)
    dt = rnd(4.0 * sp["tau"] * np.sum((1.0 - af * w_full) * g_n)
             + a1 * np.sum(w_round * g_n) + a2 * np.sum(w_round * s_n))
    levels = 2.0 ** np.maximum(q, 1) - 1.0
    qt = rnd(sp["lipschitz"] / 2.0 * np.sum(w_round * z * theta**2
                                             / (4.0 * levels**2)))
    kept = np.array([i if i >= 0 and a[i] else -1 for i in assign])
    return Decision(kept, a, np.where(a, q, 0), np.where(a, v, 0.0), energy,
                    float(dt), float(qt))


def _repair(assign: np.ndarray) -> np.ndarray:
    """Each client keeps its lowest-index channel; -1 stays unused."""
    out = np.full_like(assign, -1)
    seen = set()
    for ch, i in enumerate(assign):
        if i >= 0 and i not in seen:
            out[ch] = i
            seen.add(i)
    return out


def ga_decide(key, rates, d, g_n, s_n, theta, lam1, lam2, cfg, z, rnd, ga):
    """Genetic search over channel assignments (Algorithm 1), objective
    J0 = lam1 * data_term + lam2 * quant_term + V * energy, on the
    documented key schedule: init keys split from one half of the round's
    GA key, one generation key per generation from the other half."""
    u, c = rates.shape
    npop, ngen, el, tour = (ga["population"], ga["generations"],
                            ga["elitism"], ga["tournament"])
    m = min(u, c)
    n_child = npop - el
    n_pairs = (n_child + 1) // 2
    V = cfg["lyapunov"]["v_weight"]
    k_init, k_evolve = jax.random.split(jax.random.fold_in(key, GA_KEY_TAG))
    pop = []
    for ki in jax.random.split(k_init, npop):
        kk, ku, kc = jax.random.split(ki, 3)
        n_sched = int(jax.random.randint(kk, (), 1, m + 1))
        perm_u = np.asarray(jax.random.permutation(ku, u))
        perm_c = np.asarray(jax.random.permutation(kc, c))
        chrom = np.full(c, -1, np.int64)
        chrom[perm_c[:n_sched]] = perm_u[:n_sched]
        pop.append(chrom)
    pop = np.stack(pop)
    best, best_j = np.full(c, -1, np.int64), np.inf

    def j0_of(chrom):
        dec = finish(chrom, rates, d, g_n, s_n, theta, lam2, cfg, z, rnd)
        return rnd(lam1 * dec.data_term + lam2 * dec.quant_term
                   + V * np.sum(dec.energy))

    for kg in jax.random.split(k_evolve, ngen):
        j0 = np.array([float(j0_of(ch)) for ch in pop])
        i_star = int(np.argmin(j0))
        if j0[i_star] < best_j:
            best, best_j = pop[i_star].copy(), j0[i_star]
        k_sel, k_cx, k_pt, k_mm, k_mv = jax.random.split(kg, 5)
        cand = np.asarray(jax.random.randint(k_sel, (n_pairs, 2, tour), 0, npop))
        do_cx = np.asarray(jax.random.uniform(k_cx, (n_pairs,))) < ga["p_crossover"]
        pt = np.asarray(jax.random.randint(k_pt, (n_pairs,), 1, c))
        mut = np.asarray(jax.random.uniform(k_mm, (n_child, c))) < ga["p_mutation"]
        mut_val = np.asarray(jax.random.randint(k_mv, (n_child, c), -1, u))
        children = []
        for pr in range(n_pairs):
            p1 = pop[cand[pr, 0][np.argmin(j0[cand[pr, 0]])]]
            p2 = pop[cand[pr, 1][np.argmin(j0[cand[pr, 1]])]]
            cut = np.arange(c) < pt[pr]
            if do_cx[pr]:
                children += [_repair(np.where(cut, p1, p2)),
                             _repair(np.where(cut, p2, p1))]
            else:
                children += [p1.copy(), p2.copy()]
        children = np.stack(children[:n_child])
        children = np.stack([_repair(r) for r in
                             np.where(mut, mut_val, children)])
        elites = pop[np.argsort(j0, kind="stable")[:el]]
        pop = np.concatenate([elites, children])
    return finish(best, rates, d, g_n, s_n, theta, lam2, cfg, z, rnd)


# ------------------------------------------------------------------ rounds

def assignment_of(v_assigned: np.ndarray, rates: np.ndarray) -> tuple:
    """(C,) channel -> client of a reported decision, read from each
    scheduled client's assigned rate: the channel whose rate is within
    ``RATE_MATCH`` of it. Returns the assignment and the number of
    scheduled clients no free channel matches (left out of it)."""
    assign = np.full(rates.shape[1], -1, np.int64)
    missed = 0
    for i in np.flatnonzero(v_assigned > 0):
        rel = np.abs(rates[i] - v_assigned[i]) / v_assigned[i]
        ch = int(np.argmin(np.where(assign < 0, rel, np.inf)))
        if rel[ch] <= RATE_MATCH:
            assign[ch] = i
        else:
            missed += 1
    return assign, missed


class Reference:
    """The reference experiment: ``run(n_rounds)`` follows the first
    ``n_rounds`` rounds of an experiment of ``rounds_per_experiment``
    rounds and returns the per-round outputs the program reports.

    The genetic search ranks chromosomes by an objective dominated by a
    schedule-independent term, so in float32 its ranking of nearly equal
    chromosomes turns on rounding and a replay in float64 takes another
    path. With ``follow`` (the program's reported per-round ``rates``)
    the reference judges the program's decision instead of replaying the
    search: it reads the channel assignment from the reported rates,
    re-derives levels, frequencies, energy and queues for it, and trains
    and scores what it schedules."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 precision: Optional[str] = None, fault: Optional[str] = None,
                 follow: Optional[dict] = None):
        stated = cfg["precision"]
        storage = precision or stated["storage"]
        assert storage in ("float32", "bfloat16"), storage
        assert fault is None or fault in FAULTS, fault
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.fault = fault
        self.follow = follow
        self.bf16 = storage == "bfloat16"
        self.rnd: Callable = _to_bf16 if self.bf16 else _identity
        self.dtype = jnp.bfloat16 if self.bf16 else jnp.float32
        self.data = Workload(cfg, seed)
        model = cfg["model"]
        self.params0 = init_params(model, jax.random.PRNGKey(self.seed))
        self.z = int(flatten(self.params0).shape[0])
        self.zpad = flops.pad_len(self.z, cfg["block_m"])
        self._sgd, self._eval = _programs(json.dumps(model, sort_keys=True),
                                          cfg["training"]["lr"], storage,
                                          stated["dot"])

    def run(self, n_rounds: int) -> dict:
        cfg, rnd, z = self.cfg, self.rnd, self.z
        u, c = cfg["n_clients"], cfg["n_channels"]
        s = min(u, c)
        t = cfg["training"]
        d = self.data.sizes.astype(np.float64)
        eps1, eps2 = budgets(cfg, self.data.sizes, z)
        test_x, test_y = self.data.test_set()
        test_x, test_y = jnp.asarray(test_x), jnp.asarray(test_y)
        flat = flatten(self.params0).astype(self.dtype)
        g_sq, s_sq, theta = np.ones(u), np.ones(u), np.ones(u)
        lam1 = lam2 = 0.0
        policy = self.traffic["policy"]
        keys = jax.random.split(jax.random.PRNGKey(self.seed + 1),
                                self.traffic["rounds_per_experiment"])
        out = {k: [] for k in ("energy", "accuracy", "loss", "n_scheduled",
                               "q_levels", "rates", "lambda1", "lambda2",
                               "unmatched")}
        for r in range(n_rounds):
            k_ch, k_batch, k_quant = jax.random.split(keys[r], 3)
            rates = draw_rates(k_ch, self.data.distances, cfg, rnd)
            g_n = rnd(g_sq / max(np.mean(g_sq), 1e-12))
            s_n = rnd(s_sq / max(np.mean(s_sq), 1e-12))
            if policy == "compiled-ga" and self.follow is None:
                dec = ga_decide(keys[r], rates, d, g_n, s_n, theta, lam1, lam2,
                                cfg, z, rnd, self.traffic["ga"])
            elif policy == "compiled-ga":
                assign, missed = assignment_of(
                    np.asarray(self.follow["rates"][r]), rates)
                dec = finish(assign, rates, d, g_n, s_n, theta, lam2, cfg, z,
                             rnd)
                out["unmatched"].append(missed)
            else:
                assert policy == "greedy", policy
                dec = finish(greedy_assign(rates), rates, d, g_n, s_n, theta,
                             lam2, cfg, z, rnd)
            if self.fault == "altered" and dec.a.any():
                i = int(np.flatnonzero(dec.a)[0])
                dec.q[i] = dec.q[i] % cfg["lyapunov"]["q_cap"] + 1
            slots = [int(i) for i in dec.assign if i >= 0]  # channel order
            batch_keys = jax.random.split(k_batch, s)
            u01 = jax.random.uniform(k_quant, (s, self.zpad), jnp.float32)
            new = jnp.zeros((self.zpad,), self.dtype)
            dn = sum(d[i] for i in slots)
            for sl, i in enumerate(slots):
                x_i, y_i = self.data.client(i)
                idx = np.asarray(jax.random.randint(
                    batch_keys[sl], (t["tau"], t["batch_size"]), 0,
                    int(self.data.sizes[i])))
                if self.fault == "half_batch":
                    idx = idx[:, : t["batch_size"] // 2]
                flat_i, g_obs, s_obs = self._sgd(flat, jnp.asarray(x_i[idx]),
                                                 jnp.asarray(y_i[idx]))
                deq, th = _wire(flat_i, u01[sl], dec.q[i], self.zpad)
                new = new + jnp.asarray(rnd(d[i] / dn), self.dtype) * deq
                g_sq[i] = rnd(EMA_DECAY * g_sq[i]
                              + (1 - EMA_DECAY) * max(float(g_obs), 0.0))
                s_sq[i] = rnd(EMA_DECAY * s_sq[i]
                              + (1 - EMA_DECAY) * max(float(s_obs), SIGMA_FLOOR))
                theta[i] = float(th)
            if slots and self.fault != "unchanged":
                flat = new[:z]
            lam1 = max(lam1 + dec.data_term - eps1, 0.0)
            lam2 = max(lam2 + dec.quant_term - eps2, 0.0)
            acc, loss = self._eval(flat, test_x, test_y)
            out["energy"].append(float(np.sum(dec.energy)))
            out["accuracy"].append(float(acc))
            out["loss"].append(float(loss))
            out["n_scheduled"].append(int(dec.a.sum()))
            out["q_levels"].append(dec.q.copy())
            out["rates"].append(dec.v.copy())
            out["lambda1"].append(lam1)
            out["lambda2"].append(lam2)
        res = {k: np.asarray(v) for k, v in out.items()}
        res["eps"] = np.array([eps1, eps2])
        return res


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, lr: float, storage: str, dot: str):
    """The jitted local SGD and eval of one model, storage type and dot
    precision, shared by every reference of a process."""
    model = json.loads(model_json)
    like = jax.eval_shape(lambda k: init_params(model, k),
                          jax.random.PRNGKey(0))
    prec = DOT_PRECISION[dot]
    return (jax.jit(functools.partial(_local_sgd, model, lr, prec, like)),
            jax.jit(functools.partial(_evaluate, model, prec, like)))


def _local_sgd(model, lr, precision, like, flat, xb, yb):
    """tau plain SGD steps from the global model on the drawn batches;
    returns the trained flat model, the mean squared gradient norm and
    the variance of the squared norms over the steps."""
    p = unflatten(flat, like)
    loss_fn = lambda p, x, y: xent(forward(model, p, x, precision), y)  # noqa: E731
    gsqs = []
    for step in range(xb.shape[0]):
        grads = jax.grad(loss_fn)(p, xb[step], yb[step])
        gsqs.append(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads)))
        p = jax.tree_util.tree_map(lambda w, g: w - jnp.asarray(lr, w.dtype) * g,
                                   p, grads)
    gsqs = jnp.stack(gsqs)
    return flatten(p), jnp.mean(gsqs), jnp.var(gsqs)


def _evaluate(model, precision, like, flat, x, y):
    logits = forward(model, unflatten(flat, like), x, precision)
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    return acc, xent(logits.astype(jnp.float32), y)


@functools.partial(jax.jit, static_argnums=(3,))
def _wire(flat, u01, q, zpad):
    """Stochastic quantization of one client's model at level q (eq. 4)
    and its dequantized value, padded to ``zpad``: magnitude |x| scaled
    to 2^q - 1 levels of the range theta = max|x|, rounded up with
    probability equal to the fraction, sign kept."""
    dt = flat.dtype
    x = jnp.pad(flat, (0, zpad - flat.shape[0]))
    theta = jnp.max(jnp.abs(x))
    levels = (2.0 ** jnp.maximum(q, 1) - 1.0).astype(dt)
    safe = jnp.where(theta > 0, theta, jnp.ones((), dt))
    scaled = jnp.abs(x) * (levels / safe)
    low = jnp.floor(scaled)
    idx = jnp.minimum(low + (u01 < (scaled - low)).astype(dt), levels)
    mag = idx * (theta / levels)
    return jnp.where(x < 0, -mag, mag), theta
