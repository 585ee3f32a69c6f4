"""Compiled fast-path decision policy: greedy channels + vectorized KKT.

The QCCF controller's per-round decision is GA-over-assignments with a
per-client closed-form KKT solve in the fitness (Algorithm 1 + eq. 41/42).
The GA is host-side by nature; for 1000+-client fleets this module provides
the compiled fast path the paper's own baselines use for channel allocation:

  1. greedy channel assignment (iterated global argmax over the (U, C) rate
     matrix — identical to ``repro.fl.baselines._greedy_channels`` up to
     tie-breaks, which are measure-zero for continuous rates);
  2. infeasibility drop: clients that cannot meet T_max even at q = 1
     (``q_max_feasible < 1``) are unscheduled, exactly the repair mode of
     ``core.genetic.evaluate_assignment``;
  3. a *vectorized* jnp port of the 5-case KKT walk of
     ``repro.core.kkt.solve_continuous`` (Case-2 depressed cubic in closed
     form covering both the Cardano and casus-irreducibilis branches,
     Case-5 by fixed-iteration bisection) + Theorem-3 integerization.

``decide_host`` is the numpy oracle: the same greedy assignment + the
trusted scalar ``repro.core.kkt`` solver, used by the parity tests and by
anyone wanting the decision off-device. Both paths clamp q to ``q_cap`` so
the wire format stays in the u8/u16 index planes the kernels consume.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bounds, kkt
from repro.core.genetic import SystemParams
from repro.obs.profile import scope as _profile_scope

LN2 = math.log(2.0)
RANGE_BITS = 32.0


# ------------------------------------------------------------- assignment

def greedy_assign(rates: jax.Array) -> jax.Array:
    """(U, C) rates -> (C,) channel->client ids (-1 = unused), compiled.

    Iterated global argmax: pick the best remaining (client, channel) pair
    min(U, C) times, masking the chosen row and column each step.
    """
    u, c = rates.shape

    def body(_, carry):
        assign, row_free, col_free = carry
        masked = jnp.where(row_free[:, None] & col_free[None, :], rates, -jnp.inf)
        flat = jnp.argmax(masked)
        i, ch = flat // c, flat % c
        assign = assign.at[ch].set(i.astype(jnp.int32))
        row_free = row_free.at[i].set(False)
        col_free = col_free.at[ch].set(False)
        return assign, row_free, col_free

    carry = (
        jnp.full((c,), -1, jnp.int32),
        jnp.ones((u,), bool),
        jnp.ones((c,), bool),
    )
    assign, _, _ = jax.lax.fori_loop(0, min(u, c), body, carry)
    return assign


def greedy_assign_host(rates: np.ndarray) -> np.ndarray:
    """Numpy mirror of :func:`greedy_assign` (identical tie-breaking)."""
    rates = np.asarray(rates)
    u, c = rates.shape
    assign = np.full(c, -1, dtype=np.int64)
    row_free = np.ones(u, bool)
    col_free = np.ones(c, bool)
    for _ in range(min(u, c)):
        masked = np.where(row_free[:, None] & col_free[None, :], rates, -np.inf)
        i, ch = divmod(int(masked.argmax()), c)
        assign[ch] = i
        row_free[i] = False
        col_free[ch] = False
    return assign


# ------------------------------------------------------- vectorized KKT

@dataclasses.dataclass
class FastDecision:
    """Arrays-only decision record (the compiled Decision equivalent)."""

    assign: Any        # (C,) channel -> client
    slots: Any         # (S,) scheduled-slot client ids, -1 padded; S = min(U, C)
    a: Any             # (U,) participation {0,1}
    q: Any             # (U,) integer levels (0 if out)
    f: Any             # (U,) CPU frequency (0 if out)
    v_assigned: Any    # (U,) assigned uplink rate (0 if out)
    energy: Any        # (U,)
    latency: Any       # (U,)
    data_term: Any     # scalar
    quant_term: Any    # scalar
    payload_bits: Any  # scalar
    q_cont: Any        # (U,) continuous pre-integerization q (telemetry tap):
    #                    the Theorem-3 clipped q_hat for KKT policies, the raw
    #                    policy level for baselines; meaningful only where a > 0.


# All-array dataclass; registering it as a pytree lets compiled decision
# functions (decide, search.ga_decide) return one across a jit boundary.
jax.tree_util.register_dataclass(
    FastDecision,
    data_fields=[f.name for f in dataclasses.fields(FastDecision)],
    meta_fields=[],
)


def compact_slots(assign: jax.Array, n_clients: int) -> jax.Array:
    """(C,) kept assignment -> fixed-width (S,) scheduled-slot client ids.

    S = min(U, C) is static, so the engine's per-round tensors can live on
    the slot axis (active-set compaction) while the scan stays one compile.
    Assigned channels come first in ascending channel order (stable sort of
    the emptiness mask), then -1 padding; the assignment is injective after
    repair, so each scheduled client owns exactly one slot.
    """
    s = min(n_clients, int(assign.shape[0]))
    order = jnp.argsort(assign < 0)  # jnp sorts are stable
    return jnp.take(assign, order[:s]).astype(jnp.int32)


def compact_slots_host(assign: np.ndarray, n_clients: int) -> np.ndarray:
    """Numpy mirror of :func:`compact_slots` (same slot order)."""
    assign = np.asarray(assign)
    s = min(n_clients, assign.shape[0])
    order = np.argsort(assign < 0, kind="stable")
    return assign[order[:s]].astype(np.int64)


def _s_of_q(v, d, q, sysp: SystemParams, z: int):
    """Latency-tight frequency S(q), inf when the deadline is unmeetable."""
    slack = v * sysp.t_max - (z * q + z + RANGE_BITS)
    f_req = v * sysp.tau_e * sysp.gamma * d / jnp.maximum(slack, 1e-30)
    return jnp.where(slack > 0, jnp.maximum(sysp.f_min, f_req), jnp.inf)


def _latency(v, d, f, q, sysp: SystemParams, z: int):
    return sysp.tau_e * sysp.gamma * d / f + (z * q + z + RANGE_BITS) / v


def _j3(v, w, d, theta, lam, q, f, sysp: SystemParams, z: int, v_weight: float):
    levels = 2.0**q - 1.0
    quant = lam * w * z * sysp.lipschitz * theta**2 / (8.0 * levels**2)
    cmp_e = v_weight * sysp.tau_e * sysp.alpha * sysp.gamma * d * f**2
    com_e = sysp.p_tx * v_weight * z * q / v
    return quant + cmp_e + com_e


def _g_of_q(q, lam, w, theta, sysp: SystemParams):
    """G(q) = 2^q ln2 lam w L theta^2 / (4 (2^q - 1)^3).

    Clamped to 0 past q = 60, where G ~ 2^{-2q} is already ~1e-36: the
    cutoff must sit well below fp32's 2^128 overflow (2^q -> inf -> NaN
    near q = 128), unlike the host solver's f64 cutoff at 128. The host
    value over (60, 128] is below every comparison threshold, so case
    selection is unaffected.
    """
    y = 2.0 ** jnp.minimum(q, 60.0)
    g = y * LN2 * lam * w * sysp.lipschitz * theta**2 / (
        4.0 * jnp.maximum(y - 1.0, 1e-30) ** 3
    )
    return jnp.where(q > 60.0, 0.0, g)


def _case2_cubic(a4):
    """Largest positive real root of y^3 - A4 y - A4 = 0, both branches.

    Depressed cubic with p = q = -A4. For A4 <= 27/4 the discriminant
    A4^2/4 - A4^3/27 is nonnegative (Cardano, unique real root); beyond it
    the trigonometric form picks the largest of the three real roots —
    matching the host solver's ``max(positive roots of np.roots)``.
    """
    a4 = jnp.maximum(a4, 1e-30)
    disc = a4**2 / 4.0 - a4**3 / 27.0
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    y_card = jnp.cbrt(a4 / 2.0 + sq) + jnp.cbrt(a4 / 2.0 - sq)
    arg = jnp.clip(1.5 * jnp.sqrt(3.0 / a4), -1.0, 1.0)
    y_trig = 2.0 * jnp.sqrt(a4 / 3.0) * jnp.cos(jnp.arccos(arg) / 3.0)
    return jnp.where(disc >= 0.0, y_card, y_trig)


def solve_kkt(
    v: jax.Array,       # (U,) assigned uplink rate
    w: jax.Array,       # (U,) round weights a_i D_i / D^n
    d: jax.Array,       # (U,) dataset sizes
    theta: jax.Array,   # (U,) theta_max
    lam: jax.Array,     # scalar (lambda2 - eps2_for_kkt)
    sysp: SystemParams,
    z: int,
    v_weight: float,
    q_cap: int = 8,
    grid_n: int = 512,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Vectorized eq. 41/42: returns (q int, f, feasible, q_cont) per client.

    Walks the same 5 mutually exclusive KKT cases as
    ``repro.core.kkt.solve_continuous`` in its priority order (1, 2, 4, 3,
    5, grid fallback), then Theorem-3 floor/ceil integerization clamped to
    ``q_cap``. ``q_cont`` is the continuous clipped q_hat the
    integerization started from (the telemetry tap behind
    ``RoundMetrics.q_cont_mean``). Everything is elementwise over the
    clients given; :func:`finish_decision` gives the scheduled ones only.
    """
    p, V = sysp.p_tx, v_weight
    L = sysp.lipschitz
    v_safe = jnp.maximum(v, 1e-6)

    qmax = (v_safe * sysp.t_max
            - sysp.tau_e * sysp.gamma * d * v_safe / sysp.f_max
            - z - RANGE_BITS) / z
    feasible = qmax >= 1.0

    # Case 1: C8' tight (q = 1).
    pre1 = p * V - 0.5 * v_safe * w * L * lam * theta**2 * LN2 >= 0.0
    f1 = _s_of_q(v_safe, d, 1.0, sysp, z)
    ok1 = pre1 & (f1 <= sysp.f_max)

    # Case 2: latency loose, f = f_min, q from the depressed cubic.
    a4 = v_safe * w * L * lam * theta**2 * LN2 / (4.0 * p * V)
    q2 = jnp.log2(1.0 + _case2_cubic(a4))
    ok2 = (a4 > 0.0) & (q2 > 1.0) & (
        _latency(v_safe, d, sysp.f_min, q2, sysp, z) < sysp.t_max
    )

    # Cases 4/3: latency tight, f pinned at a bound (host checks 4 first).
    def pinned(f_pin):
        slack = v_safe * sysp.t_max - v_safe * sysp.tau_e * sysp.gamma * d / f_pin
        q_pin = (slack - z - RANGE_BITS) / z
        kappa1 = v_safe * _g_of_q(q_pin, lam, w, theta, sysp) - p * V
        return q_pin, kappa1

    q4, kap4 = pinned(sysp.f_min)
    ok4 = (q4 > 1.0) & (kap4 >= 0.0) & (kap4 <= 2.0 * V * sysp.alpha * sysp.f_min**3)
    q3, kap3 = pinned(sysp.f_max)
    ok3 = (q3 > 1.0) & (kap3 >= 0.0) & (kap3 >= 2.0 * V * sysp.alpha * sysp.f_max**3)

    # Case 5: interior — bisection on h(q) over (1, qmax), 80 halvings as
    # in the host solver.
    def h_of(q):
        den = jnp.maximum(v_safe * sysp.t_max - (z * q + z + RANGE_BITS), 1e-30)
        f = v_safe * sysp.tau_e * sysp.gamma * d / den
        return (v_safe * _g_of_q(q, lam, w, theta, sysp) / V
                - p - 2.0 * sysp.alpha * f**3)

    lo0 = jnp.full_like(v_safe, 1.0 + 1e-9)
    hi0 = qmax - 1e-9
    bracket = (lam > 0.0) & (qmax > 1.0) & (hi0 > lo0) \
        & (h_of(lo0) >= 0.0) & (h_of(hi0) <= 0.0)

    def bis(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        up = h_of(mid) > 0.0
        return jnp.where(up, mid, lo), jnp.where(up, hi, mid)

    lo, hi = jax.lax.fori_loop(0, 80, bis, (lo0, jnp.maximum(hi0, lo0)))
    q5 = 0.5 * (lo + hi)
    f5 = _s_of_q(v_safe, d, q5, sysp, z)
    ok5 = bracket & (q5 > 1.0) & (sysp.f_min < f5) & (f5 < sysp.f_max)

    # Fallback: dense grid over feasible q (same 512 points as the host).
    span = jnp.maximum(qmax, 1.0) - 1.0
    qs = 1.0 + span[:, None] * jnp.linspace(0.0, 1.0, grid_n)[None, :]  # (U, G)
    fs = _s_of_q(v_safe[:, None], d[:, None], qs, sysp, z)
    js = jnp.where(
        fs <= sysp.f_max,
        _j3(v_safe[:, None], w[:, None], d[:, None], theta[:, None],
            lam, qs, fs, sysp, z, v_weight),
        jnp.inf,
    )
    q0 = jnp.take_along_axis(qs, jnp.argmin(js, axis=1)[:, None], axis=1)[:, 0]

    # Priority select (host order: 1, 2, 4, 3, 5, fallback).
    q_hat = q0
    q_hat = jnp.where(ok5, q5, q_hat)
    q_hat = jnp.where(ok3, q3, q_hat)
    q_hat = jnp.where(ok4, q4, q_hat)
    q_hat = jnp.where(ok2, q2, q_hat)
    q_hat = jnp.where(ok1, 1.0, q_hat)

    # Theorem 3 integerization, clamped to the wire format's q_cap.
    q_hat = jnp.clip(q_hat, 1.0, float(q_cap))
    q_lo = jnp.maximum(jnp.floor(q_hat), 1.0)
    q_hi = jnp.maximum(jnp.ceil(q_hat), 1.0)

    def j_of(qq):
        f = _s_of_q(v_safe, d, qq, sysp, z)
        # fp32 tolerance: q at the exact qmax boundary gives f == f_max up
        # to rounding (the f64 host solver accepts it); clamp back into C5.
        ok = (f <= sysp.f_max * (1.0 + 1e-5))
        f = jnp.minimum(f, sysp.f_max)
        lat = _latency(v_safe, d, f, qq, sysp, z)
        ok = ok & (lat <= sysp.t_max * (1.0 + 1e-5))
        return jnp.where(ok, _j3(v_safe, w, d, theta, lam, qq, f, sysp, z,
                                 v_weight), jnp.inf), f

    j_lo, f_lo = j_of(q_lo)
    j_hi, f_hi = j_of(q_hi)
    take_hi = j_hi < j_lo  # ties keep floor, as the host's sorted scan does
    q_int = jnp.where(take_hi, q_hi, q_lo)
    f_int = jnp.where(take_hi, f_hi, f_lo)
    feasible = feasible & jnp.isfinite(jnp.where(take_hi, j_hi, j_lo))
    return q_int.astype(jnp.int32), f_int, feasible, q_hat


# --------------------------------------------------------- bound terms

def data_term(consts: bounds.BoundConstants, a, w_full, w_round, g_sq, sigma_sq,
              hetero=None):
    """jnp port of :func:`repro.core.bounds.data_term` (eq. 20).

    ``hetero`` is the (U,) heterogeneity scheduling multiplier (>= 1, from
    the scenario's ``hetero_weight`` x per-client label-KL): it scales only
    the scheduling-exclusion component, making label-skewed clients more
    expensive to leave out. ``None`` or all-ones is the heterogeneity-blind
    eq. 20 bit for bit (IEEE multiply by 1.0 is exact).
    """
    g_sched = g_sq if hetero is None else g_sq * hetero
    sched = 4.0 * consts.tau * jnp.sum((1.0 - a * w_full) * g_sched)
    drift = consts.a1 * jnp.sum(w_round * g_sq) + consts.a2 * jnp.sum(w_round * sigma_sq)
    return sched + drift


def quant_term(consts: bounds.BoundConstants, w_round, z, theta_max, q):
    """jnp port of :func:`repro.core.bounds.quant_term` (eq. 21)."""
    levels = jnp.maximum(2.0 ** q.astype(jnp.float32) - 1.0, 1e-12)
    per_client = z * theta_max**2 / (4.0 * levels**2)
    return consts.lipschitz / 2.0 * jnp.sum(w_round * per_client)


def realized_terms(a_real, d_sizes, g_sq, sigma_sq, theta_max, q, sysp,
                   z, hetero=None, dl_term=None):
    """jnp port of :func:`repro.core.bounds.realized_terms` — eq. 20/21 at
    the *realized* (post-screen) participation, the queue feedback the
    fault-tolerant engine uses instead of the planned decision terms.

    A scheduled-but-failed client re-enters the scheduling-exclusion sum
    and leaves the round weights, exactly like an unscheduled one; all
    other inputs are the same ones the decision saw (normalized G^2 /
    sigma^2, pre-update theta_max, the decision's q), so with zero realized
    faults this reproduces ``finish_decision``'s terms bit for bit (same
    ops, same order).
    """
    af = a_real.astype(jnp.float32)
    d_n = jnp.sum(af * d_sizes)
    w_round = jnp.where(a_real > 0, af * d_sizes / jnp.maximum(d_n, 1e-12),
                        0.0)
    w_full = d_sizes / jnp.sum(d_sizes)
    consts = sysp.bound_constants()
    dt = data_term(consts, af, w_full, w_round, g_sq, sigma_sq, hetero)
    qt = quant_term(consts, w_round, z, theta_max, jnp.maximum(q, 1))
    if dl_term is not None:
        qt = qt + dl_term
    return dt, qt


# --------------------------------------------------------------- decide

def participation_from_assign(assign: jax.Array, rates: jax.Array):
    """(C,) chromosome -> ((U,) assigned rate, (U,) bool participation)."""
    u = rates.shape[0]
    onehot = (assign[None, :] == jnp.arange(u)[:, None]) & (assign[None, :] >= 0)
    v_assigned = jnp.sum(jnp.where(onehot, rates, 0.0), axis=1)
    return v_assigned, onehot.any(axis=1)


def solve_scheduled(
    assign: jax.Array,     # (C,) channel -> client (-1 unused)
    v: jax.Array,          # (U,) assigned uplink rate
    w: jax.Array,          # (U,) round weights
    d: jax.Array,          # (U,) dataset sizes
    theta: jax.Array,      # (U,) theta_max
    lam: jax.Array,        # scalar
    sysp: SystemParams,
    z: int,
    v_weight: float,
    q_cap: int = 8,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`solve_kkt` for the clients ``assign`` schedules, as (U,) arrays.

    Every output of :func:`finish_decision` is masked by participation, so
    the solve runs on the slot axis of S = min(U, C) entries: the channel
    axis when C < U (a free channel's slot is solved on zeros and dropped),
    else the client axis. Gather and scatter are sums over the (U, C)
    one-hot with one non-zero term, exact for finite values and cheaper on
    the TPU than an indexed gather; the assignment is injective, as
    :func:`greedy_assign` and the GA's repair leave it. Clients off the
    assignment get q = 0, f = 0, feasible False and q_hat 0, as in
    :func:`finish_host`.
    """
    u, c = v.shape[0], assign.shape[0]
    onehot = assign[None, :] == jnp.arange(u)[:, None]  # -1 matches no client
    if u <= c:
        out = solve_kkt(v, w, d, theta, lam, sysp, z, v_weight, q_cap=q_cap)
        sched = onehot.any(axis=1)
        return tuple(jnp.where(sched, y, jnp.zeros((), y.dtype)) for y in out)

    def gather(x):
        return jnp.sum(jnp.where(onehot, x[:, None], 0.0), axis=0)

    def scatter(y):
        if y.dtype == jnp.bool_:
            return jnp.any(onehot & y[None, :], axis=1)
        return jnp.sum(jnp.where(onehot, y[None, :], jnp.zeros((), y.dtype)), axis=1)

    out = solve_kkt(gather(v), gather(w), gather(d), gather(theta), lam, sysp,
                    z, v_weight, q_cap=q_cap)
    return tuple(scatter(y) for y in out)


def finish_decision(
    assign: jax.Array,     # (C,) channel -> client (-1 unused)
    v_assigned: jax.Array, # (U,) assigned uplink rate
    a0: jax.Array,         # (U,) bool pre-drop participation
    d_sizes: jax.Array,    # (U,)
    g_sq: jax.Array,       # (U,) normalized G^2 estimates
    sigma_sq: jax.Array,   # (U,)
    theta_max: jax.Array,  # (U,)
    lam2: jax.Array,       # scalar lambda2 queue (sound form: lam = lambda2)
    sysp: SystemParams,
    z: int,
    v_weight: float,
    q_cap: int = 8,
    hetero=None,       # (U,) scheduling multiplier (None = hetero-blind)
    dl_term=None,      # scalar: previous round's realized downlink bound term
) -> FastDecision:
    """Steps 2-3 of the fast path for ANY channel assignment: infeasibility
    drop + vectorized KKT + bound terms. Shared by the greedy :func:`decide`
    and by the compiled GA fitness (``repro.sim.search``), which evaluates
    every chromosome through exactly this code path.

    ``dl_term`` (when the engine broadcasts a quantized downlink) is the
    previous round's realized ``bounds.downlink_term`` — added to the
    returned ``quant_term`` so the lambda2 queue (and through it every
    subsequent KKT solve) sees the server->client error. It is constant
    across assignments, so the within-round argmin is unchanged; ``None``
    (downlink off) traces the exact pre-downlink program."""
    u = d_sizes.shape[0]

    # Feasibility does not depend on w or the queues, so one drop pass
    # suffices (the repair loop of evaluate_assignment converges in one
    # iteration for any fixed assignment).
    qmax = (v_assigned * sysp.t_max
            - sysp.tau_e * sysp.gamma * d_sizes * v_assigned / sysp.f_max
            - z - RANGE_BITS) / z
    a = a0 & (qmax >= 1.0)
    af = a.astype(jnp.float32)

    d_n = jnp.sum(af * d_sizes)
    w_round = jnp.where(a, af * d_sizes / jnp.maximum(d_n, 1e-12), 0.0)
    w_full = d_sizes / jnp.sum(d_sizes)

    with _profile_scope("kkt_solve"):
        q_int, f_int, feas, q_hat = solve_scheduled(
            assign, v_assigned, w_round, d_sizes, theta_max, lam2, sysp, z,
            v_weight, q_cap=q_cap,
        )
    # feas == a's gate except in float corner cases; fold it in so q/f/energy
    # stay consistent (w_round keeps the pre-solve participation, as the
    # host repair loop would only re-weight on an actual drop).
    a = a & feas
    af = a.astype(jnp.float32)
    q = jnp.where(a, q_int, 0).astype(jnp.int32)
    f = jnp.where(a, f_int, 0.0)

    t_com = (z * q.astype(jnp.float32) + z + RANGE_BITS) / jnp.maximum(v_assigned, 1e-6)
    t_cmp = sysp.tau_e * sysp.gamma * d_sizes / jnp.maximum(f, 1.0)
    energy = jnp.where(
        a,
        sysp.tau_e * sysp.alpha * sysp.gamma * d_sizes * f**2 + sysp.p_tx * t_com,
        0.0,
    )
    latency = jnp.where(a, t_cmp + t_com, 0.0)

    consts = sysp.bound_constants()
    dt = data_term(consts, af, w_full, w_round, g_sq, sigma_sq, hetero)
    qt = quant_term(consts, w_round, z, theta_max, jnp.maximum(q, 1))
    if dl_term is not None:
        qt = qt + dl_term
    payload = jnp.sum(jnp.where(a, z * q.astype(jnp.float32) + z + RANGE_BITS, 0.0))
    # drop the -1-marked channels of clients that failed the feasibility gate
    assign_kept = jnp.where(
        (assign >= 0) & a[jnp.clip(assign, 0, u - 1)], assign, -1
    )
    return FastDecision(
        assign=assign_kept, slots=compact_slots(assign_kept, u),
        a=a.astype(jnp.int32), q=q, f=f,
        v_assigned=jnp.where(a, v_assigned, 0.0), energy=energy,
        latency=latency, data_term=dt, quant_term=qt, payload_bits=payload,
        q_cont=q_hat,
    )


def decide(
    rates: jax.Array,      # (U, C)
    d_sizes: jax.Array,    # (U,)
    g_sq: jax.Array,       # (U,) normalized G^2 estimates
    sigma_sq: jax.Array,   # (U,)
    theta_max: jax.Array,  # (U,)
    lam2: jax.Array,       # scalar lambda2 queue (sound form: lam = lambda2)
    sysp: SystemParams,
    z: int,
    v_weight: float,
    q_cap: int = 8,
    hetero=None,
    dl_term=None,
) -> FastDecision:
    """One fully traced decision round (steps 1-2 of the fast path)."""
    assign = greedy_assign(rates)
    v_assigned, a0 = participation_from_assign(assign, rates)
    return finish_decision(
        assign, v_assigned, a0, d_sizes, g_sq, sigma_sq, theta_max, lam2,
        sysp, z, v_weight, q_cap=q_cap, hetero=hetero, dl_term=dl_term,
    )


class HostFastPolicy:
    """The fast path as a host-side ``repro.fl`` Policy.

    Greedy channels + scalar ``core.kkt`` per client + sound-form Lyapunov
    queues — the numpy oracle of the compiled :func:`decide`, packaged so
    ``FLExperiment`` (object-based loop) and ``FleetSim.run_host_policy``
    (compiled executor) can both be driven by QCCF-style decisions that the
    parity tests can compare against the one-scan engine.
    """

    name = "greedy_kkt"

    def __init__(self, sysp: SystemParams, eps1: float, eps2: float,
                 v_weight: float, q_cap: int = 8, hetero=None) -> None:
        self.sysp = sysp
        self.eps1, self.eps2 = float(eps1), float(eps2)
        self.v_weight = float(v_weight)
        self.q_cap = int(q_cap)
        self.hetero = None if hetero is None else np.asarray(hetero, np.float64)
        self.lambda1 = 0.0
        self.lambda2 = 0.0
        self.dl_term = None

    def set_downlink_term(self, dl_term) -> None:
        """Engine hook (``run_host_policy``): last round's realized downlink
        bound term, mirrored into this round's quant_term like the scan."""
        self.dl_term = dl_term

    def decide(self, ctx):
        from repro.core.genetic import Decision

        fd = decide_host(
            ctx.rates, ctx.d_sizes, ctx.g_sq, ctx.sigma_sq, ctx.theta_max,
            self.lambda2, self.sysp, ctx.z, self.v_weight, q_cap=self.q_cap,
            hetero=self.hetero, dl_term=self.dl_term,
        )
        dec = Decision(
            assign=fd.assign, a=fd.a, q=fd.q, f=fd.f, energy=fd.energy,
            latency=fd.latency, j0=0.0, data_term=float(fd.data_term),
            quant_term=float(fd.quant_term), feasible=True,
        )
        # telemetry tap: the scalar solver's clipped q_hat, so host replays
        # record the same q_cont_mean the compiled scan taps (Decision is a
        # plain dataclass; the attribute rides along for run_host_policy).
        dec.q_cont = fd.q_cont
        return dec

    def commit(self, dec) -> None:
        self.lambda1 = max(self.lambda1 + dec.data_term - self.eps1, 0.0)
        self.lambda2 = max(self.lambda2 + dec.quant_term - self.eps2, 0.0)


def finish_host(
    assign: np.ndarray,
    rates: np.ndarray,
    d_sizes: np.ndarray,
    g_sq: np.ndarray,
    sigma_sq: np.ndarray,
    theta_max: np.ndarray,
    lam2: float,
    sysp: SystemParams,
    z: int,
    v_weight: float,
    q_cap: int = 8,
    hetero: np.ndarray | None = None,
    dl_term: float | None = None,
) -> FastDecision:
    """Numpy mirror of :func:`finish_decision` for ANY assignment: the
    per-client solve goes through the trusted scalar ``repro.core.kkt``.
    Shared by :func:`decide_host` and the host GA oracle
    (``repro.sim.search.run_ga_host``)."""
    u = rates.shape[0]
    v_assigned = np.zeros(u)
    for ch, cid in enumerate(assign):
        if cid >= 0:
            v_assigned[cid] += rates[cid, ch]
    a = v_assigned > 0

    def env_for(i, w):
        return kkt.ClientEnv(
            v=float(v_assigned[i]), w=float(w), d_size=float(d_sizes[i]),
            z=z, theta_max=float(theta_max[i]), lambda2=float(lam2), eps2=0.0,
            v_weight=v_weight, p=sysp.p_tx, alpha=sysp.alpha, gamma=sysp.gamma,
            tau_e=sysp.tau_e, t_max=sysp.t_max, f_min=sysp.f_min,
            f_max=sysp.f_max, lipschitz=sysp.lipschitz,
        )

    for i in range(u):
        if a[i] and kkt.q_max_feasible(env_for(i, 0.0)) < 1.0:
            a[i] = False
    d_n = float(np.sum(a * d_sizes))
    w_round = np.where(a, a * d_sizes / max(d_n, 1e-12), 0.0)
    w_full = d_sizes / np.sum(d_sizes)

    q = np.zeros(u, np.int64)
    f = np.zeros(u)
    energy = np.zeros(u)
    latency = np.zeros(u)
    q_cont = np.zeros(u)
    for i in range(u):
        if not a[i]:
            continue
        env = env_for(i, w_round[i])
        q_hat, _f_hat, case = kkt.solve_continuous(env)
        assert case != -1, "feasibility pre-filtered above"
        q_cont[i] = float(np.clip(q_hat, 1.0, q_cap))
        dec = kkt.integerize(env, q_cont[i])
        assert dec is not None
        q[i], f[i] = dec.q, dec.f
        energy[i] = dec.energy
        latency[i] = dec.latency

    consts = sysp.bound_constants()
    af = a.astype(np.float64)
    dt = bounds.data_term(consts, af, w_full, w_round, g_sq, sigma_sq, hetero)
    qt = bounds.quant_term(consts, w_round, z, theta_max, np.maximum(q, 1))
    if dl_term is not None:
        qt = qt + float(dl_term)
    payload = float(np.sum(np.where(a, z * q + z + RANGE_BITS, 0.0)))
    assign_kept = np.where((assign >= 0) & a[np.clip(assign, 0, u - 1)], assign, -1)
    return FastDecision(
        assign=assign_kept, slots=compact_slots_host(assign_kept, u),
        a=a.astype(np.int64), q=q, f=f,
        v_assigned=np.where(a, v_assigned, 0.0), energy=energy,
        latency=latency, data_term=dt, quant_term=qt, payload_bits=payload,
        q_cont=q_cont,
    )


def decide_host(
    rates: np.ndarray,
    d_sizes: np.ndarray,
    g_sq: np.ndarray,
    sigma_sq: np.ndarray,
    theta_max: np.ndarray,
    lam2: float,
    sysp: SystemParams,
    z: int,
    v_weight: float,
    q_cap: int = 8,
    hetero: np.ndarray | None = None,
    dl_term: float | None = None,
) -> FastDecision:
    """Numpy oracle for :func:`decide`: greedy assignment + scalar KKT."""
    return finish_host(
        greedy_assign_host(rates), rates, d_sizes, g_sq, sigma_sq, theta_max,
        lam2, sysp, z, v_weight, q_cap=q_cap, hetero=hetero, dl_term=dl_term,
    )


# ----------------------------------------------------- compiled baselines
#
# The paper's Sec.-VI baselines (repro.fl.baselines) as traced decision
# functions, selected by the scenario pytree's ``policy`` field so
# QCCF-vs-baseline curves run inside the engine's one-compile scan at any
# fleet size. Accounting mirrors ``fl.baselines._energies`` +
# ``FleetSim.run_host_policy``'s wire clamp exactly (bit-for-bit parity at
# U = 8 is regressed in tests/test_sim_baselines.py):
#
#   * energy/latency/bound terms are computed at the policy's RAW q (e.g.
#     q = 32 for NoQuant) on the pre-timeout participation — timed-out
#     clients still burn their energy, the "principle" pathology;
#   * the ``q`` field / slots / payload are clamped into the wire format
#     (``q_cap``), matching what run_host_policy executes and records;
#   * baselines are heterogeneity-BLIND: no ``hetero`` argument, like
#     their host counterparts.
#
# ``same_size`` needs the GA and therefore lives in ``repro.sim.search``
# (importing it here would be circular).

def account_baseline(
    assign: jax.Array,     # (C,) channel -> client (-1 unused)
    rates: jax.Array,      # (U, C)
    d_sizes: jax.Array,
    g_sq: jax.Array,
    sigma_sq: jax.Array,
    theta_max: jax.Array,
    q_raw: jax.Array,      # (U,) the policy's chosen levels, float, unclamped
    f: jax.Array,          # (U,) chosen CPU frequency
    sysp: SystemParams,
    z: int,
    q_cap: int,
    drop_late: bool = False,
    late_tol: float = 1.0,   # drop when latency > t_max * late_tol
) -> FastDecision:
    """Traced mirror of ``fl.baselines._energies`` (+ the optional
    latency-timeout drop of PrinciplePolicy/SameSizePolicy) packaged as a
    FastDecision the engine's compacted round body can execute."""
    u = d_sizes.shape[0]
    v_assigned, a0 = participation_from_assign(assign, rates)
    af0 = a0.astype(jnp.float32)
    v_safe = jnp.maximum(v_assigned, 1e-6)

    bits = z * q_raw + z + RANGE_BITS
    t_com = bits / v_safe
    t_cmp = sysp.tau_e * sysp.gamma * d_sizes / jnp.maximum(f, 1.0)
    energy = jnp.where(
        a0,
        sysp.tau_e * sysp.alpha * sysp.gamma * d_sizes * f**2
        + sysp.p_tx * t_com,
        0.0,
    )
    latency = jnp.where(a0, t_cmp + t_com, 0.0)

    d_n = jnp.sum(af0 * d_sizes)
    w_round = jnp.where(a0, af0 * d_sizes / jnp.maximum(d_n, 1e-12), 0.0)
    w_full = d_sizes / jnp.sum(d_sizes)
    consts = sysp.bound_constants()
    dt = data_term(consts, af0, w_full, w_round, g_sq, sigma_sq)
    qt = quant_term(consts, w_round, z, theta_max, jnp.maximum(q_raw, 1.0))

    # PrinciplePolicy semantics: clients past the deadline drop out of the
    # aggregation (a = 0) AFTER the terms above were accounted — their
    # energy stays spent and their latency stays on the record.
    a = a0 & ~(latency > sysp.t_max * late_tol) if drop_late else a0

    # Wire clamp, as run_host_policy applies to host decisions: the index
    # plane is sized for q_cap levels, so records/slots carry clipped q.
    q_wire = jnp.clip(q_raw.astype(jnp.int32), 1, q_cap) * a.astype(jnp.int32)
    payload = jnp.sum(jnp.where(
        a, z * jnp.maximum(q_wire, 1).astype(jnp.float32) + z + RANGE_BITS, 0.0
    ))
    assign_kept = jnp.where(
        (assign >= 0) & a[jnp.clip(assign, 0, u - 1)], assign, -1
    )
    # run_host_policy records latency 0 when nothing was scheduled at all
    latency = jnp.where(jnp.any(a), latency, 0.0)
    return FastDecision(
        assign=assign_kept, slots=compact_slots(assign_kept, u),
        a=a.astype(jnp.int32), q=q_wire, f=jnp.where(a0, f, 0.0),
        v_assigned=jnp.where(a0, v_assigned, 0.0), energy=energy,
        latency=latency, data_term=dt, quant_term=qt, payload_bits=payload,
        q_cont=q_raw,
    )


def baseline_no_quant(
    rates, d_sizes, g_sq, sigma_sq, theta_max, sysp: SystemParams, z: int,
    q_cap: int,
) -> FastDecision:
    """Traced ``fl.baselines.NoQuantPolicy``: fp32 uploads (q = 32),
    f = f_max to race the deadline."""
    u = d_sizes.shape[0]
    assign = greedy_assign(rates)
    q = jnp.full((u,), 32.0)
    f = jnp.full((u,), sysp.f_max)
    return account_baseline(assign, rates, d_sizes, g_sq, sigma_sq,
                            theta_max, q, f, sysp, z, q_cap)


def baseline_channel_allocate(
    rates, d_sizes, g_sq, sigma_sq, theta_max, sysp: SystemParams, z: int,
    q_cap: int, q_policy_cap: int = 16,
) -> FastDecision:
    """Traced ``fl.baselines.ChannelAllocatePolicy``: greedy channels, the
    largest q that fits T_max at f_max, then f relaxed to the latency
    boundary — channel-adaptive, training-oblivious."""
    u = d_sizes.shape[0]
    sp = sysp
    assign = greedy_assign(rates)
    v_assigned, a0 = participation_from_assign(assign, rates)
    v_safe = jnp.maximum(v_assigned, 1e-6)
    t_cmp = sp.tau_e * sp.gamma * d_sizes / sp.f_max
    budget_bits = v_safe * (sp.t_max - t_cmp)
    q_i = jnp.floor((budget_bits - z - RANGE_BITS) / z)
    q = jnp.where(a0, jnp.clip(q_i, 1.0, float(q_policy_cap)), 1.0)
    env_bits = z * q + z + RANGE_BITS
    slack = sp.t_max - env_bits / v_safe
    f_req = sp.tau_e * sp.gamma * d_sizes / jnp.maximum(slack, 1e-30)
    f = jnp.where(a0 & (slack > 0),
                  jnp.clip(f_req, sp.f_min, sp.f_max), sp.f_max)
    return account_baseline(assign, rates, d_sizes, g_sq, sigma_sq,
                            theta_max, q, f, sysp, z, q_cap)


def baseline_principle(
    round_idx, rates, d_sizes, g_sq, sigma_sq, theta_max,
    sysp: SystemParams, z: int, q_cap: int,
    q0: float = 2.0, double_every: int = 30, q_policy_cap: int = 16,
) -> FastDecision:
    """Traced ``fl.baselines.PrinciplePolicy`` (DAdaQuant-flavoured [24]):
    q doubles on a fixed round schedule and scales with dataset size, no
    wireless awareness — f pinned at f_max, deadline-missers time out.
    ``round_idx`` is the traced scan round (the host policy's counter)."""
    u = d_sizes.shape[0]
    assign = greedy_assign(rates)
    base = q0 * 2.0 ** (round_idx // double_every).astype(jnp.float32)
    size_scale = d_sizes / jnp.mean(d_sizes)
    q = jnp.clip(jnp.round(base * size_scale), 1.0, float(q_policy_cap))
    f = jnp.full((u,), sysp.f_max)
    return account_baseline(assign, rates, d_sizes, g_sq, sigma_sq,
                            theta_max, q, f, sysp, z, q_cap, drop_late=True)
