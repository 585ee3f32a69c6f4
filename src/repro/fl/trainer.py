"""The 5-step FL round loop (paper Fig. 1) with pluggable decision policies.

One ``FLExperiment`` = server + U clients + wireless simulator + a policy
(QCCF or a baseline from ``repro.fl.baselines``). Each round:
  1. Decision   : policy produces (a, R, q, f) from channel states + stats
  2. Broadcast  : global model to scheduled clients (downlink, free)
  3. Local+Quant: tau local SGD steps, then q_i-bit stochastic quantization
  4. Upload     : energy/latency accounted from eq. 14-17
  5. Aggregate  : theta^n = sum_i w_i^n Q(theta_i^{n,tau})   (eq. 2)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantization
from repro.core.genetic import Decision, RoundContext, SystemParams
from repro.fl.client import FLClient
from repro.obs.profile import span
from repro.wireless.channel import ChannelModel

Pytree = Any


@dataclasses.dataclass
class RoundRecord:
    round: int
    energy: float
    cum_energy: float
    accuracy: float
    loss: float
    n_scheduled: int
    q_levels: np.ndarray
    latency: float
    payload_bits: float
    # per-client assigned uplink rate [bit/s], 0 where unscheduled — q_i is
    # driven jointly by (v_i, D_i), so analyses of Remark 1/2 behaviour need
    # the realized rate to condition on.
    rates: Optional[np.ndarray] = None


@dataclasses.dataclass
class ExperimentResult:
    name: str
    records: list[RoundRecord]

    @property
    def cum_energy(self) -> np.ndarray:
        return np.array([r.cum_energy for r in self.records])

    @property
    def accuracy(self) -> np.ndarray:
        return np.array([r.accuracy for r in self.records])

    def summary(self) -> dict:
        last = self.records[-1]
        return {
            "name": self.name,
            "rounds": len(self.records),
            "final_accuracy": last.accuracy,
            "total_energy_J": last.cum_energy,
            "mean_q": float(np.mean([r.q_levels[r.q_levels > 0].mean()
                                     for r in self.records if (r.q_levels > 0).any()] or [0])),
        }


class Policy:
    """Interface: produce a Decision each round, observe the outcome."""

    name = "policy"

    def decide(self, ctx: RoundContext) -> Decision:
        raise NotImplementedError

    def commit(self, dec: Decision) -> None:
        pass


class FLExperiment:
    def __init__(
        self,
        clients: list[FLClient],
        init_params: Pytree,
        eval_fn: Callable[[Pytree], tuple[float, float]],  # -> (acc, loss)
        channel: ChannelModel,
        sysp: SystemParams,
        policy: Policy,
        *,
        lr: float = 0.05,
        seed: int = 0,
        theta_max_fn: Optional[Callable[[Pytree], float]] = None,
    ) -> None:
        self.clients = clients
        self.params = init_params
        self.eval_fn = eval_fn
        self.channel = channel
        self.sysp = sysp
        self.policy = policy
        self.lr = lr
        self.key = jax.random.PRNGKey(seed)
        self.z = quantization.pytree_size(init_params)
        self.d_sizes = np.array([c.d_size for c in clients], dtype=np.float64)
        # online estimator state (EMA of G^2 / sigma^2 per client)
        u = len(clients)
        self.g_sq = np.full(u, 1.0)
        self.sigma_sq = np.full(u, 1.0)
        self.theta_max = np.full(u, 1.0)
        self._cum_energy = 0.0

    def _context(self) -> RoundContext:
        # G_i^2 / sigma_i^2 enter the bound terms linearly, so only their
        # RELATIVE per-client magnitudes inform scheduling; the absolute
        # scale is what eps1 is calibrated against. Normalizing to mean 1
        # keeps the queue dynamics stationary as the true gradient norms
        # shrink during training (otherwise lambda1 starves and the
        # controller stops scheduling — see DESIGN.md §6).
        g = self.g_sq / max(float(np.mean(self.g_sq)), 1e-12)
        s = self.sigma_sq / max(float(np.mean(self.sigma_sq)), 1e-12)
        return RoundContext(
            rates=self.channel.draw_rates(),
            d_sizes=self.d_sizes,
            g_sq=g,
            sigma_sq=s,
            theta_max=self.theta_max.copy(),
            z=self.z,
        )

    def run(self, n_rounds: int, eval_every: int = 1, verbose: bool = False
            ) -> ExperimentResult:
        records: list[RoundRecord] = []
        acc, loss = self.eval_fn(self.params)
        for n in range(n_rounds):
            ctx = self._context()
            with span("fl_decide"):
                dec = self.policy.decide(ctx)
            v_assigned = np.zeros(len(self.clients))
            for c, cid in enumerate(dec.assign):
                if cid >= 0:
                    v_assigned[cid] += float(ctx.rates[cid, c])

            uploads = []
            weights = []
            d_n = float(np.sum(dec.a * self.d_sizes))
            payload = 0.0
            with span("fl_local_quant"):
                for i, client in enumerate(self.clients):
                    if not dec.a[i]:
                        continue
                    theta_i, g_sq, sig_sq = client.local_update(
                        self.params, self.sysp.tau, self.lr
                    )
                    self.g_sq[i] = 0.7 * self.g_sq[i] + 0.3 * g_sq
                    self.sigma_sq[i] = (
                        0.7 * self.sigma_sq[i] + 0.3 * max(sig_sq, 1e-8)
                    )
                    self.key, sub = jax.random.split(self.key)
                    q_i = int(max(dec.q[i], 1))
                    quantized, tmax = quantization.quantize_pytree(
                        sub, theta_i, q_i
                    )
                    self.theta_max[i] = float(tmax)
                    uploads.append(quantized)
                    weights.append(self.d_sizes[i] / d_n)
                    payload += quantization.payload_bits(self.z, q_i)

            if uploads:
                with span("fl_aggregate"):
                    new = jax.tree_util.tree_map(
                        lambda *leaves: sum(
                            w * l for w, l in zip(weights, leaves)
                        ),
                        *uploads,
                    )
                self.params = new

            self.policy.commit(dec)
            self._cum_energy += dec.total_energy
            if (n + 1) % eval_every == 0 or n == n_rounds - 1:
                acc, loss = self.eval_fn(self.params)
            records.append(
                RoundRecord(
                    round=n,
                    energy=dec.total_energy,
                    cum_energy=self._cum_energy,
                    accuracy=acc,
                    loss=loss,
                    n_scheduled=int(dec.a.sum()),
                    q_levels=dec.q.copy(),
                    latency=float(dec.latency.max() if dec.a.any() else 0.0),
                    payload_bits=payload,
                    rates=v_assigned,
                )
            )
            if verbose:
                print(
                    f"[{self.policy.name}] r{n:03d} acc={acc:.3f} "
                    f"E={self._cum_energy:.3f}J sched={int(dec.a.sum())} "
                    f"q={dec.q[dec.a.astype(bool)] if dec.a.any() else []}"
                )
        return ExperimentResult(self.policy.name, records)
