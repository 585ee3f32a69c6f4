"""The compiled fleet simulator: a whole FL experiment as one lax.scan.

``build_sim`` mirrors ``repro.fl.experiment.build_experiment`` setup (same
synthetic datasets, same client drop, same eps1/eps2 calibration, same
initial model for a given seed), then ``FleetSim.run_compiled`` executes
every round inside a single jitted ``lax.scan``:

  decision   — compiled greedy + vectorized KKT (``repro.sim.policy``), the
               in-trace GA (``repro.sim.search``), or one of the paper's
               baselines as a traced decision function — selected by the
               scenario pytree's ``policy`` field (``repro.sim.scenario``)
  channel    — traced Rician/UMa rate draws (``repro.sim.channel``), (A, U)
               cell-free geometry with the distances as a dynamic jit
               argument (scenarios sharing a pytree structure share one
               compiled scan)
  compaction — ``jnp.take`` the S = min(U, C) scheduled clients' rows onto
               the fixed slot axis (``FastDecision.slots``); everything
               below is O(S), not O(U)
  local work — vmapped tau-step SGD for the S active slots (``sim.fleet``)
  aggregate  — quantize S wire planes -> fused dequant+weighted-sum through
               the tiled Pallas kernel (``repro.kernels.stochastic_quant``),
               which accumulates over a client grid axis — any S, no dense
               einsum fallback
  scatter    — masked ``.at[].add`` of the slot observations back into the
               (U,) G²/σ²/θ EMA estimators in the scan carry
  queues     — Lyapunov lambda1/lambda2 updates carried in the scan state

No per-client Python objects exist at run time: the fleet is four stacked
arrays, the decision bookkeeping is (U,)-vectorized, and the per-round
training/wire work is (S,)-compacted. The fleet arrays and the test set
enter every jitted program as ARGUMENTS (``FleetSim.data``), never as
closed-over constants: they stay on the device once placed, and the
lowered program's size does not grow with the fleet's bytes (gated in
tests/test_tpu_compile.py). ``run_host_policy`` is the per-round
fallback engine that lets the host-side GA controller (``QCCFController``)
or any ``repro.fl`` Policy drive the same compiled (and equally compacted)
round execution when the closed-form fast path is not wanted; it replays
the scan's slot derivation and key schedule bit for bit (see the
``repro.sim.fleet`` docstring for the per-slot key contract).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from repro.core import bounds
from repro.core import quantization as core_quant
from repro.core.genetic import GAConfig, RoundContext, SystemParams
from repro.obs import ledger as obs_ledger
from repro.obs import metrics as obs_metrics
from repro.obs.metrics import MetricsConfig
from repro.obs.profile import scope, span
from repro.data.synthetic import (
    SyntheticImageTask, gaussian_sizes, hetero_kl, make_federated_datasets,
    make_test_set,
)
from repro.fl.trainer import ExperimentResult, RoundRecord
from repro.kernels import stochastic_quant as sq
from repro.models import cnn
from repro.sim import channel as sim_channel
from repro.sim import policy as fast_policy
from repro.sim import search
from repro.sim.channel import SimChannel
from repro.sim.fleet import (
    Fleet, build_fleet, ema_update, fleet_local_sgd, gather_active,
    scatter_slots,
)
from repro.sim.scenario import FAULTS_OFF, FaultSpec, Scenario, get_scenario
from repro.wireless.channel import ChannelModel, ChannelParams

Pytree = Any
LANES = sq.LANES

# fold_in tag deriving the cell-free client-drop key from the seed (kept
# away from the model-init / round-key streams).
DROP_KEY_TAG = 7
# fold_in tag for the eps-probe rate draw when no host ChannelModel exists
# (cell-free topologies; single-BS setups probe the numpy model instead).
PROBE_KEY_TAG = 8
# fold_in tag deriving the downlink-broadcast quantization key from the
# ROUND key (same tag as launch.steps.DOWNLINK_KEY_TAG): a separate stream,
# so switching the downlink on never perturbs the channel/batch/uplink
# uniforms and downlink-off runs stay bit-identical to the two-leg engine.
DOWNLINK_KEY_TAG = 13
# fold_in tag deriving the per-round fault stream (outage / fade / wire
# corruption / gradient bursts, see scenario.FaultSpec) from the ROUND key:
# its own stream, so switching faults on never perturbs the
# channel/batch/uplink/downlink/GA uniforms — faults-off runs stay
# bit-identical to the fault-free engine, and run_host_policy replays the
# draws bit for bit by folding the same tag.
FAULT_KEY_TAG = 17


def fault_keys(round_key: jax.Array):
    """(k_outage, k_fade, k_corrupt, k_burst) for one round — the shared
    traced/eager derivation both engines use."""
    return jax.random.split(jax.random.fold_in(round_key, FAULT_KEY_TAG), 4)


def draw_outage(k_out: jax.Array, out_state: jax.Array, fv: jax.Array):
    """(U,) bool outage draw from the (optionally Markov) client process.

    ``out_state`` is the carried previous-round state (1.0 = was down);
    ``fv`` the dyn fault vector. P(down | was down) = p + corr (1 - p),
    P(down | was up) = p (1 - corr): corr = 0 is exactly i.i.d. and the
    stationary outage rate is p for any corr.
    """
    p, corr = fv[0], fv[1]
    thresh = jnp.where(out_state > 0, p + corr * (1.0 - p), p * (1.0 - corr))
    return jax.random.uniform(k_out, out_state.shape) < thresh


def draw_fade(k_fade: jax.Array, n_clients: int, fv: jax.Array):
    """((U,) bool fade hit, (U,) realized-rate multiplier: fade_mult where
    hit, 1.0 elsewhere)."""
    hit = jax.random.uniform(k_fade, (n_clients,)) < fv[2]
    return hit, jnp.where(hit, fv[3], 1.0)


def inject_burst(k_burst: jax.Array, slots: jax.Array, flat_s: jax.Array,
                 fv: jax.Array):
    """NaN/Inf gradient bursts: with prob nan_p a scheduled slot's local
    update is replaced (half the bursts NaN, half +Inf) BEFORE the wire, so
    its range scalar theta is non-finite and the screen rejects it."""
    u01 = jax.random.uniform(k_burst, (flat_s.shape[0],))
    hit = (u01 < fv[6]) & (slots >= 0)
    val = jnp.where(u01 < 0.5 * fv[6], jnp.float32(jnp.nan),
                    jnp.float32(jnp.inf))
    return jnp.where(hit[:, None], val[:, None], flat_s)


def corrupt_planes(k_corr: jax.Array, idx: jax.Array, signs: jax.Array,
                   fv: jax.Array):
    """Wire corruption: with prob corrupt_p a slot's index + sign planes
    get a corrupt_frac fraction of entries XORed with random bytes (same
    flip sites and bytes for both planes — one event corrupts the slot's
    wire). Detected by the range screen (index > 2^q - 1 / sign byte > 1);
    an undetected index flip still lands inside [-theta, theta] through the
    clamped dequantizer."""
    k_hit, k_site, k_bits = jax.random.split(k_corr, 3)
    hit = jax.random.uniform(k_hit, (idx.shape[0],)) < fv[4]
    site = jax.random.uniform(k_site, idx.shape) < fv[5]
    flip = hit[:, None] & site
    bits = jax.random.randint(k_bits, idx.shape, 0, 256, jnp.int32)
    idx_c = jnp.where(flip, jnp.bitwise_xor(idx.astype(jnp.int32), bits),
                      idx.astype(jnp.int32)).astype(idx.dtype)
    signs_c = jnp.where(flip, jnp.bitwise_xor(signs.astype(jnp.int32), bits),
                        signs.astype(jnp.int32)).astype(signs.dtype)
    return idx_c, signs_c


def screen_slots(slots, q_slot, d_slot, v_slot, f_slot, theta, idx, signs,
                 down_u, fade_mult_u, fade_hit_u, sysp, z):
    """The graceful-degradation screen: per-slot delivery verdict + fault
    counters, shared verbatim by the scan body and the host-replay
    executor (bit-for-bit replay).

    A slot delivers iff it was scheduled AND not in outage AND its realized
    (fade-scaled) round time meets t_max AND its range scalar is finite AND
    its wire planes pass the range check (index <= 2^q - 1, sign byte <=
    1). The latency arithmetic mirrors ``policy.finish_decision`` with the
    fade multiplier on the assigned rate, so an un-faded slot can never be
    screened as a timeout (the planned decision already enforced t_max).

    Returns ``(ok, n_dropped, n_timeout_real, n_screened)`` — n_screened
    counts every scheduled-but-failed slot (outage + realized timeout +
    corrupt/non-finite payloads).
    """
    sm = slots >= 0
    cid = jnp.maximum(slots, 0)
    drop = jnp.take(down_u, cid) & sm
    f_hit = jnp.take(fade_hit_u, cid) & sm
    mult = jnp.take(fade_mult_u, cid)
    qf = jnp.maximum(q_slot, 1).astype(jnp.float32)
    t_com = (z * qf + z + fast_policy.RANGE_BITS) / jnp.maximum(
        v_slot * mult, 1e-6)
    t_cmp = sysp.tau_e * sysp.gamma * d_slot / jnp.maximum(f_slot, 1.0)
    timeout = f_hit & (t_cmp + t_com > sysp.t_max)
    plane_ok = sq.plane_in_range(idx, q_slot) & (
        jnp.max(signs, axis=1) <= 1)
    ok = sm & ~drop & ~timeout & jnp.isfinite(theta) & plane_ok
    f32 = jnp.float32
    return (ok,
            jnp.sum(drop.astype(f32)),
            jnp.sum(timeout.astype(f32)),
            jnp.sum((sm & ~ok).astype(f32)))


@dataclasses.dataclass(frozen=True)
class DownlinkConfig:
    """Static gate for the server->client broadcast wire (frozen + hashable:
    it selects a trace, it never rides through one).

    mode    "off"   — fp32 broadcast, the pre-downlink engine bit for bit
                      (the scan carry stays a 6-tuple and the lowered HLO is
                      byte-identical, regressed in tests/test_obs.py);
            "quant" — stochastically quantize the global aggregate at
                      ``q_bits`` (paper eq. 4 on the flat model, one shared
                      range) and carry the DEQUANTIZED model into the next
                      round's local SGD;
            "delta" — quantize the aggregate-minus-previous-broadcast delta
                      instead; clients reconstruct prev + deq(delta). Every
                      client holds the same previous broadcast, so one
                      payload serves the fleet.
    q_bits  downlink quantization level (the broadcast payload is
            Z*q_bits + Z + 32 bits, mirroring the uplink eq. 5 format).
    """

    mode: str = "off"
    q_bits: int = 8

    def __post_init__(self) -> None:
        if self.mode not in ("off", "quant", "delta"):
            raise ValueError(
                f"downlink mode must be off/quant/delta, got {self.mode!r}"
            )
        if not 1 <= int(self.q_bits) <= 16:
            raise ValueError(
                f"downlink q_bits={self.q_bits} outside the wire format's "
                "1..16 (uint16 index plane, see core.quantization)"
            )

    @property
    def enabled(self) -> bool:
        return self.mode != "off"


DOWNLINK_OFF = DownlinkConfig()

# scenario-pytree policy names -> engine modes (the engine keeps its
# historical mode names; scenarios speak the POLICIES vocabulary)
POLICY_MODE_ALIASES = {"qccf": "greedy", "qccf_ga": "compiled-ga"}
_BASELINE_MODES = ("no_quant", "channel_allocate", "principle", "same_size")


@dataclasses.dataclass
class SimResult:
    """Stacked per-round arrays — the RoundRecord columns, (N,...)-shaped."""

    name: str
    energy: np.ndarray        # (N,)
    accuracy: np.ndarray      # (N,)
    loss: np.ndarray          # (N,)
    n_scheduled: np.ndarray   # (N,)
    q_levels: np.ndarray      # (N, U)
    latency: np.ndarray       # (N,)
    payload_bits: np.ndarray  # (N,)
    rates: np.ndarray         # (N, U) assigned uplink rates
    lambda1: np.ndarray       # (N,)
    lambda2: np.ndarray       # (N,)
    # telemetry taps ({field: (N,) array}, see repro.obs.metrics) — None
    # unless the sim was built with telemetry enabled
    metrics: Optional[dict] = None

    @property
    def cum_energy(self) -> np.ndarray:
        return np.cumsum(self.energy)

    def to_result(self) -> ExperimentResult:
        """Adapt to the object-based ``ExperimentResult`` API."""
        cum = self.cum_energy
        records = [
            RoundRecord(
                round=n,
                energy=float(self.energy[n]),
                cum_energy=float(cum[n]),
                accuracy=float(self.accuracy[n]),
                loss=float(self.loss[n]),
                n_scheduled=int(self.n_scheduled[n]),
                q_levels=self.q_levels[n].copy(),
                latency=float(self.latency[n]),
                payload_bits=float(self.payload_bits[n]),
                rates=self.rates[n].copy(),
            )
            for n in range(len(self.energy))
        ]
        return ExperimentResult(self.name, records)


def _pad_len(z: int, block_m: int) -> int:
    tile = block_m * LANES
    return ((z + tile - 1) // tile) * tile


def _quantize_wire(key: jax.Array, flat_s: jax.Array, q: jax.Array,
                   q_cap: int, zpad: int):
    """(S, Z) slot params + per-slot traced q -> Zpad-shaped wire planes.

    Same stochastic rounding as ``core.quantization.quantize_indices`` but
    vectorized over the slot axis with a traced per-slot level; the index
    plane dtype is sized statically from ``q_cap``. The planes come out
    already padded to the kernel tile (``zpad``) — padding coordinates are
    exact zeros, so they quantize to index 0 / sign 0 and the scan body
    carries no per-round re-padding. ``theta`` is the range over the real
    Z coordinates (the zero padding never raises a max of |x|).

    Key contract: the stochastic-rounding uniforms are one ``(S, zpad)``
    draw from ``key`` — replays must quantize the same compacted slot
    matrix to reproduce the stream.
    """
    theta = jnp.max(jnp.abs(flat_s), axis=1)                     # (S,)
    flat_p = jnp.pad(flat_s, ((0, 0), (0, zpad - flat_s.shape[1])))
    safe = jnp.where(theta > 0, theta, 1.0)
    levels = 2.0 ** jnp.maximum(q, 1).astype(jnp.float32) - 1.0  # (S,)
    scaled = jnp.abs(flat_p) * (levels / safe)[:, None]
    lower = jnp.floor(scaled)
    frac = scaled - lower
    u01 = jax.random.uniform(key, flat_p.shape, jnp.float32)
    idx = jnp.minimum(lower + (u01 < frac).astype(jnp.float32), levels[:, None])
    dtype = jnp.uint8 if q_cap <= 8 else jnp.uint16
    return idx.astype(dtype), (flat_p < 0).astype(jnp.uint8), theta


class FleetSim:
    """Holds the static setup; ``run_compiled`` is the one-scan experiment."""

    def __init__(
        self,
        fleet: Fleet,
        init_params: Pytree,
        loss_fn,
        eval_fn,                    # traced (flat_params, test) -> (acc, loss)
        channel: SimChannel,
        sysp: SystemParams,
        *,
        test_set: dict,             # {"x", "y"} arrays eval_fn scores on
        eps1: float,
        eps2: float,
        v_weight: float = 100.0,
        lr: float = 0.05,
        batch_size: int = 32,
        q_cap: int = 8,
        block_m: int = 64,
        seed: int = 0,
        host_channel: Optional[ChannelModel] = None,
        policy_mode: str = "greedy",  # engine mode or scenario policy name
        ga_config: Optional[GAConfig] = None,
        hetero: Optional[np.ndarray] = None,  # (U,) scheduling multiplier
        scenario: Optional[Scenario] = None,
        name: str = "sim_qccf",
        telemetry: Optional[MetricsConfig] = None,
        ledger: Optional[obs_ledger.Ledger] = None,
        downlink: Optional[DownlinkConfig] = None,
        faults: Optional[FaultSpec] = None,
    ) -> None:
        flat0, unravel = ravel_pytree(init_params)
        self.flat0 = flat0.astype(jnp.float32)
        self.unravel = unravel
        self.z = int(flat0.shape[0])
        self.fleet = fleet
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.test_set = {"x": jnp.asarray(test_set["x"]),
                         "y": jnp.asarray(test_set["y"])}
        self.channel = channel
        self.sysp = sysp
        self.eps1, self.eps2 = float(eps1), float(eps2)
        self.v_weight = float(v_weight)
        self.lr = float(lr)
        self.batch_size = int(batch_size)
        self.q_cap = int(q_cap)
        self.block_m = int(block_m)
        self._zpad = _pad_len(self.z, self.block_m)
        self.seed = int(seed)
        self.host_channel = host_channel
        policy_mode = POLICY_MODE_ALIASES.get(policy_mode, policy_mode)
        assert policy_mode in (
            ("greedy", "host-ga", "compiled-ga") + _BASELINE_MODES
        ), policy_mode
        self.policy_mode = policy_mode
        self.hetero = None if hetero is None else np.asarray(hetero, np.float64)
        self.scenario = scenario
        # Dynamic jit-argument leaves of the scenario: everything continuous
        # a sweep varies (AP geometry -> distances, the heterogeneity
        # multiplier, the eps budgets) enters the compiled scan as an
        # argument, NOT a closed-over constant — scenarios sharing a pytree
        # structure (same shapes / policy / association) share ONE compiled
        # scan, gated zero-retrace in tests/test_scenario.py.
        u = fleet.n_clients
        self._dyn = {
            "distances": jnp.asarray(channel.distances, jnp.float32),
            "hetero": (jnp.ones((u,), jnp.float32) if hetero is None
                       else jnp.asarray(hetero, jnp.float32)),
            "eps": jnp.array([self.eps1, self.eps2], jnp.float32),
        }
        # Engine default: repair (drop infeasible clients), the same
        # semantics as the greedy fast path's feasibility gate; pass an
        # explicit GAConfig for the paper's fitness-0 rule.
        if ga_config is None:
            ga_config = GAConfig(repair_infeasible=True)
        self.ga_config = ga_config
        self.name = name
        # Telemetry (repro.obs): the STATIC metrics gate selects what the
        # scan traces (off = byte-identical pre-telemetry program, see
        # tests/test_obs.py), the ledger is the JSONL sink run_compiled /
        # run_host_policy write headers + per-round rows through.
        self.metrics_cfg = obs_metrics.METRICS_OFF if telemetry is None else telemetry
        self.ledger = ledger if ledger is not None else obs_ledger.Ledger(None)
        # Downlink wire (static gate like the metrics config): "off" keeps
        # the 6-tuple carry and the byte-identical pre-downlink trace.
        self.downlink = DOWNLINK_OFF if downlink is None else downlink
        # Fault injection (static gate, scenario.FaultSpec): all-zero rates
        # trace the fault-free engine byte for byte; when enabled only the
        # VALUES ride dyn["faults"], so a fault-rate sweep shares one
        # compiled scan (tests/test_sim_faults.py gates both).
        self.faults = FAULTS_OFF if faults is None else faults
        if self.faults.enabled:
            self._dyn["faults"] = jnp.asarray(self.faults.dyn_vector())
        self._compiled: dict = {}
        self._runs = 0  # experiments run so far (the host spans' run id)
        # set by shard_clients: the mesh, and the mesh axes the fleet's
        # client rows are sharded over (None: not sharded)
        self._mesh = None
        self._client_axes = None

    def data(self) -> dict:
        """The device-resident jit arguments every compiled program takes
        first: the stacked fleet arrays and the test set. Passing them (not
        closing over them) keeps the lowered program independent of the
        fleet's bytes; ``shard_clients`` re-places them."""
        return {"x": self.fleet.x, "y": self.fleet.y,
                "n_samples": self.fleet.n_samples, "test": self.test_set}

    def _traced_fleet(self, data: dict) -> Fleet:
        return dataclasses.replace(self.fleet, x=data["x"], y=data["y"],
                                   n_samples=data["n_samples"])

    # ------------------------------------------------------------ round body

    def _aggregate(self, idx, signs, theta, w_slot, q_slot):
        """Masked eq.-2 aggregation over S wire planes -> (Zpad,) fp32.

        One code path for every active-set size: the tiled Pallas kernel
        accumulates over its client grid axis, so there is no small-K
        static-unroll limit and no dense ``(U, Zpad)`` einsum fallback.
        The planes arrive Zpad-shaped from ``_quantize_wire``. On a
        client-sharded mesh (``shard_clients``) the kernel runs under
        ``shard_map`` with every operand replicated: XLA cannot partition a
        Mosaic kernel, and the S slot planes are small next to the fleet.
        """
        s = idx.shape[0]
        agg = functools.partial(sq.aggregate, block_m=self.block_m)
        if self._mesh is not None:
            agg = jax.shard_map(agg, mesh=self._mesh, in_specs=P(),
                                out_specs=P(), check_vma=False)
        out = agg(
            idx.reshape(s, -1, LANES),
            signs.reshape(s, -1, LANES),
            theta,
            w_slot,
            jnp.maximum(q_slot, 1),
        )
        return out.reshape(-1)

    def _downlink_apply(self, round_key, new_flat, flat):
        """Quantized server->client broadcast of the aggregated model.

        Returns ``(bcast, dl_next)``: the dequantized model every client
        starts the next round from (replacing the exact aggregate in the
        carry), and the realized downlink bound term
        L/2 * Z theta_d^2 / (4 (2^q - 1)^2) that the NEXT round's decision
        adds to its quant_term (``bounds.downlink_term``; the error enters
        the clients' training one round after the broadcast that injected
        it). Quantization is ``core.quantization.quantize_array`` — the
        paper's eq.-4 stochastic rounding on the flat model with one shared
        range — keyed by ``fold_in(round_key, DOWNLINK_KEY_TAG)`` so the
        channel/batch/uplink streams are untouched. ``delta`` mode encodes
        aggregate - previous broadcast at the (smaller) delta range.
        """
        k_down = jax.random.fold_in(round_key, DOWNLINK_KEY_TAG)
        dl = self.downlink
        if dl.mode == "quant":
            deq, theta_d = core_quant.quantize_array(k_down, new_flat, dl.q_bits)
            bcast = deq
        else:
            deq, theta_d = core_quant.quantize_array(
                k_down, new_flat - flat, dl.q_bits
            )
            bcast = flat + deq
        levels = 2.0 ** float(dl.q_bits) - 1.0
        dl_next = (self.sysp.lipschitz / 2.0 * self.z * theta_d**2
                   / (4.0 * levels**2)).astype(jnp.float32)
        return bcast, dl_next

    def _decide(self, key, ridx, rates, fleet, dyn, g_sq, sigma_sq,
                theta_max, lam1, lam2, dl_prev):
        """The round's decision under ``policy_mode``: returns ``(dec,
        ga_stats, g_n, s_n, d_sizes)``, the normalised estimators and the
        client sizes the rest of the round reuses."""
        sysp, z = self.sysp, self.z
        mode = self.policy_mode
        mcfg = self.metrics_cfg
        g_n = g_sq / jnp.maximum(jnp.mean(g_sq), 1e-12)
        s_n = sigma_sq / jnp.maximum(jnp.mean(sigma_sq), 1e-12)
        d_sizes = fleet.n_samples.astype(jnp.float32)
        # static gate: GA fitness taps only exist in the trace when asked
        ga_stats = None
        tap_ga = mcfg.enabled and mcfg.ga_fitness
        if mode == "compiled-ga":
            # Full Algorithm 1 inside the trace: GA over channel assignments
            # with the KKT fitness. The GA key derives from the ROUND key
            # (not k_ch) so greedy-mode streams stay byte-identical to the
            # two-mode engine; run_host_policy mirrors this fold_in.
            k_ga = jax.random.fold_in(key, search.GA_KEY_TAG)
            if tap_ga:
                dec, ga_stats = search.ga_decide(
                    k_ga, rates, d_sizes, g_n, s_n, theta_max, lam1, lam2,
                    sysp, z, self.v_weight, cfg=self.ga_config,
                    q_cap=self.q_cap, hetero=dyn["hetero"], dl_term=dl_prev,
                    with_stats=True,
                )
            else:
                dec = search.ga_decide(
                    k_ga, rates, d_sizes, g_n, s_n, theta_max, lam1, lam2,
                    sysp, z, self.v_weight, cfg=self.ga_config,
                    q_cap=self.q_cap, hetero=dyn["hetero"], dl_term=dl_prev,
                )
        elif mode == "same_size":
            # SameSize [26] runs the same GA machinery on a mean-size fake
            # context; same GA key derivation as compiled-ga.
            k_ga = jax.random.fold_in(key, search.GA_KEY_TAG)
            if tap_ga:
                dec, ga_stats = search.baseline_same_size(
                    k_ga, rates, d_sizes, g_n, s_n, theta_max, lam1, lam2,
                    sysp, z, self.v_weight, cfg=self.ga_config,
                    q_cap=self.q_cap, with_stats=True,
                )
            else:
                dec = search.baseline_same_size(
                    k_ga, rates, d_sizes, g_n, s_n, theta_max, lam1, lam2,
                    sysp, z, self.v_weight, cfg=self.ga_config,
                    q_cap=self.q_cap,
                )
        elif mode == "no_quant":
            dec = fast_policy.baseline_no_quant(
                rates, d_sizes, g_n, s_n, theta_max, sysp, z, self.q_cap,
            )
        elif mode == "channel_allocate":
            dec = fast_policy.baseline_channel_allocate(
                rates, d_sizes, g_n, s_n, theta_max, sysp, z, self.q_cap,
            )
        elif mode == "principle":
            dec = fast_policy.baseline_principle(
                ridx, rates, d_sizes, g_n, s_n, theta_max, sysp, z,
                self.q_cap,
            )
        else:
            # dl_term: QCCF policies (greedy KKT / compiled-ga above) fold
            # the previous broadcast's error into their lambda2 queue input;
            # the paper baselines stay downlink-blind like their host
            # counterparts (the broadcast still runs on the wire).
            dec = fast_policy.decide(
                rates, d_sizes, g_n, s_n, theta_max, lam2, sysp, z,
                self.v_weight, q_cap=self.q_cap, hetero=dyn["hetero"],
                dl_term=dl_prev,
            )
        return dec, ga_stats, g_n, s_n, d_sizes

    def _round_body(self, data, dyn, carry, xs, with_eval: bool):
        flat, g_sq, sigma_sq, theta_max, lam1, lam2 = carry[:6]
        tail = 6
        dl_prev = None
        out_state = None
        if self.downlink.enabled:
            # 7th carry slot: last round's realized downlink bound term
            dl_prev = carry[tail]
            tail += 1
        if self.faults.enabled:
            # trailing carry slot: the (U,) Markov outage state (1.0 = the
            # client was in outage last round), see scenario.FaultSpec
            out_state = carry[tail]
        key, ridx = xs
        sysp, z = self.sysp, self.z
        # Each stage of the round runs under one of obs.profile's
        # ROUND_SCOPES (HLO metadata only: the lowered program is the same)
        with scope("channel_draw"):
            k_ch, k_batch, k_quant = jax.random.split(key, 3)
            if self.faults.enabled:
                fv = dyn["faults"]
                k_out, k_fade, k_corr, k_burst = fault_keys(key)
                down_u = draw_outage(k_out, out_state, fv)
                fade_hit_u, fade_mult_u = draw_fade(
                    k_fade, self.fleet.n_clients, fv)
                new_out_state = down_u.astype(jnp.float32)
            rates = sim_channel.draw_rates(
                k_ch, self.channel.params, dyn["distances"],
                self.channel.association,
            )
        fleet = self._traced_fleet(data)
        mode = self.policy_mode
        mcfg = self.metrics_cfg
        with scope("decision"):
            dec, ga_stats, g_n, s_n, d_sizes = self._decide(
                key, ridx, rates, fleet, dyn, g_sq, sigma_sq, theta_max,
                lam1, lam2, dl_prev)
        # ---- active-set compaction: O(U) work ends with the decision.
        # Everything below lives on the fixed S = min(U, C) slot axis.
        u = self.fleet.n_clients
        with scope("slot_gather"):
            slots = dec.slots                              # (S,) ids, -1 pad
            sm = slots >= 0
            cid = jnp.maximum(slots, 0)
            params = self.unravel(flat)
            x_s, y_s, n_s = gather_active(fleet, slots, mesh=self._mesh,
                                          client_axes=self._client_axes)
        stacked, g_obs, s_obs = fleet_local_sgd(
            self.loss_fn, sysp.tau, self.batch_size, params,
            x_s, y_s, n_s, self.lr, k_batch,
        )
        with scope("quantize_wire"):
            flat_s = jax.vmap(lambda p: ravel_pytree(p)[0])(stacked)  # (S, Z)
            if self.faults.enabled:
                flat_s = inject_burst(k_burst, slots, flat_s, fv)

            q_slot = jnp.take(dec.q, cid) * sm.astype(jnp.int32)
            idx, signs, theta = _quantize_wire(
                k_quant, flat_s, q_slot, self.q_cap, self._zpad
            )
            d_slot = jnp.take(d_sizes, cid) * sm.astype(jnp.float32)
            if self.faults.enabled:
                # wire corruption, then the graceful-degradation screen: a
                # screened slot's weight AND payload are zeroed (theta = NaN
                # with w = 0 would still poison the aggregate coefficient)
                # and the eq.-2 weights renormalize over the survivors.
                idx, signs = corrupt_planes(k_corr, idx, signs, fv)
                v_slot = jnp.take(dec.v_assigned, cid) * sm.astype(
                    jnp.float32)
                f_slot = jnp.take(dec.f, cid) * sm.astype(jnp.float32)
                ok, n_dropped, n_timeout_real, n_screened = screen_slots(
                    slots, q_slot, d_slot, v_slot, f_slot, theta, idx, signs,
                    down_u, fade_mult_u, fade_hit_u, sysp, z,
                )
                theta = jnp.where(ok, theta, 0.0)
                flat_s = jnp.where(ok[:, None], flat_s, 0.0)
                d_eff = d_slot * ok.astype(jnp.float32)
            else:
                d_eff = d_slot
            d_n = jnp.sum(d_eff)
            w_slot = d_eff / jnp.maximum(d_n, 1e-12)       # eq. 2 weights
        agg = self._aggregate(idx, signs, theta, w_slot, q_slot)
        with scope("server_update"):
            new_flat = jnp.where(d_n > 0, agg[: self.z], flat)
        if self.downlink.enabled:
            # the carried model becomes what the CLIENTS reconstruct from
            # the quantized broadcast — next round's local SGD (and the
            # eval below) start from it, like the real wire would
            exact_flat = new_flat
            with scope("downlink"):
                new_flat, dl_next = self._downlink_apply(key, new_flat, flat)

        with scope("server_update"):
            if self.faults.enabled:
                # graceful degradation, server side: only delivered slots
                # feed the G^2 / sigma^2 / theta estimators, and the
                # Lyapunov queues get the REALIZED eq.-20/21 terms — a
                # scheduled-but-failed client re-enters the
                # scheduling-exclusion sum exactly like an unscheduled one,
                # so the controller adapts q and scheduling to the observed
                # outage rate. Same hetero / downlink routing as the
                # decision (the baselines stay queue-blind there too).
                a_real_u = scatter_slots(slots, ok.astype(jnp.float32), u)
                use_ctx = mode in ("greedy", "compiled-ga")
                dt_real, qt_real = fast_policy.realized_terms(
                    a_real_u, d_sizes, g_n, s_n, theta_max, dec.q, sysp, z,
                    hetero=dyn["hetero"] if use_ctx else None,
                    dl_term=dl_prev if use_ctx else None,
                )
                g_sq = ema_update(
                    g_sq, scatter_slots(slots, jnp.where(ok, g_obs, 0.0), u),
                    a_real_u)
                sigma_sq = ema_update(
                    sigma_sq,
                    scatter_slots(slots, jnp.where(ok, s_obs, 0.0), u),
                    a_real_u, floor=1e-8)
                theta_max = jnp.where(a_real_u > 0,
                                      scatter_slots(slots, theta, u),
                                      theta_max)
                lam1 = jnp.maximum(lam1 + dt_real - dyn["eps"][0], 0.0)
                lam2 = jnp.maximum(lam2 + qt_real - dyn["eps"][1], 0.0)
            else:
                g_sq = ema_update(g_sq, scatter_slots(slots, g_obs, u), dec.a)
                sigma_sq = ema_update(sigma_sq,
                                      scatter_slots(slots, s_obs, u),
                                      dec.a, floor=1e-8)
                theta_max = jnp.where(dec.a > 0,
                                      scatter_slots(slots, theta, u),
                                      theta_max)
                lam1 = jnp.maximum(lam1 + dec.data_term - dyn["eps"][0], 0.0)
                lam2 = jnp.maximum(lam2 + dec.quant_term - dyn["eps"][1],
                                   0.0)

        if with_eval:
            with scope("eval"):
                acc, loss = self.eval_fn(new_flat, data["test"])
        else:
            acc, loss = jnp.float32(0.0), jnp.float32(0.0)
        with scope("server_update"):
            out = {
                "energy": jnp.sum(dec.energy),
                "accuracy": acc,
                "loss": loss,
                "n_scheduled": jnp.sum(dec.a),
                "q_levels": dec.q,
                "latency": jnp.max(dec.latency),
                "payload_bits": dec.payload_bits,
                "rates": dec.v_assigned,
                "lambda1": lam1,
                "lambda2": lam2,
            }
        if mcfg.enabled:
            # telemetry taps ride the scan as extra ys — every op here is
            # behind the static gate, so telemetry=off traces the exact
            # pre-telemetry program (HLO identity, tests/test_obs.py)
            with scope("telemetry"):
                rm = obs_metrics.decision_metrics(
                    dec.a, dec.q, dec.q_cont, dec.f, dec.energy, d_sizes,
                    dec.data_term, dec.quant_term, sysp,
                )
                if mcfg.quant_mse:
                    # realized wire error vs the unquantized eq.-2 aggregate
                    exact = jnp.einsum("s,sz->z", w_slot, flat_s)
                    mse = jnp.sum((agg[: self.z] - exact) ** 2) / self.z
                    rm = dataclasses.replace(
                        rm, quant_mse=jnp.where(d_n > 0, mse,
                                                jnp.float32(float("nan"))),
                    )
                if ga_stats is not None:
                    rm = dataclasses.replace(
                        rm, ga_best=ga_stats["ga_best"],
                        ga_median=ga_stats["ga_median"],
                    )
                if self.downlink.enabled:
                    # broadcast payload (analytic eq.-5 format) + realized
                    # broadcast error vs the exact aggregate
                    dl_bits = jnp.float32(core_quant.payload_bits(
                        self.z, self.downlink.q_bits))
                    rm = dataclasses.replace(rm, dl_payload_bits=dl_bits)
                    if mcfg.quant_mse:
                        dl_mse = jnp.sum((new_flat - exact_flat) ** 2) / self.z
                        rm = dataclasses.replace(rm, dl_mse=dl_mse)
                if self.faults.enabled:
                    rm = dataclasses.replace(
                        rm, n_dropped=n_dropped, n_screened=n_screened,
                        n_timeout_real=n_timeout_real,
                    )
                out["metrics"] = rm
        new_carry = (new_flat, g_sq, sigma_sq, theta_max, lam1, lam2)
        if self.downlink.enabled:
            new_carry = new_carry + (dl_next,)
        if self.faults.enabled:
            new_carry = new_carry + (new_out_state,)
        return new_carry, out

    # ---------------------------------------------------------------- runs

    def _init_carry(self):
        u = self.fleet.n_clients
        carry = (
            self.flat0,
            jnp.ones((u,), jnp.float32),
            jnp.ones((u,), jnp.float32),
            jnp.ones((u,), jnp.float32),
            jnp.float32(0.0),
            jnp.float32(0.0),
        )
        if self.downlink.enabled:
            carry = carry + (jnp.float32(0.0),)  # dl_prev: no broadcast yet
        if self.faults.enabled:
            # Markov outage state: every client starts up
            carry = carry + (jnp.zeros((u,), jnp.float32),)
        return carry

    def _scan_xs(self, n_rounds: int):
        """The scan's per-round inputs: (round keys, round indices). The
        round index feeds round-scheduled policies (``principle``)."""
        keys = jax.random.split(jax.random.PRNGKey(self.seed + 1), n_rounds)
        return keys, jnp.arange(n_rounds, dtype=jnp.int32)

    def _scan_fn(self, with_eval: bool):
        """jit(run(data, dyn, carry, keys, ridx)) — the fleet + test set
        (:meth:`data`) and the scenario's dynamic leaves (``_dyn``:
        distances/hetero/eps) are jit ARGUMENTS, so re-running with a
        structurally identical scenario's leaves hits the cache (zero
        retrace) and nothing fleet-sized is baked into the program."""

        def run(data, dyn, carry, keys, ridx):
            def body(c, xs):
                return self._round_body(data, dyn, c, xs, with_eval)

            return jax.lax.scan(body, carry, (keys, ridx))

        return jax.jit(run)

    def _jitted_scan(self, with_eval: bool):
        """The one jitted scan per eval mode that :meth:`lower` and the run
        paths share, so compiling a lowering ahead of time also serves the
        next :meth:`run_compiled` of the same length."""
        fn = self._compiled.get(with_eval)
        if fn is None:
            fn = self._compiled[with_eval] = self._scan_fn(with_eval)
        return fn

    def lower(self, n_rounds: int, with_eval: bool = False):
        """Trace + lower the full n_rounds scan without executing (dry run)."""
        keys, ridx = self._scan_xs(n_rounds)
        return self._jitted_scan(with_eval).lower(
            self.data(), self._dyn, self._init_carry(), keys, ridx
        )

    def _np_out(self, out) -> dict:
        """Scan ys pytree -> plain nested numpy dict (telemetry flattened
        to a {field: (N,)} sub-dict) — the segment / checkpoint / result
        interchange format."""
        d = {k: np.asarray(v) for k, v in out.items() if k != "metrics"}
        if "metrics" in out:
            d["metrics"] = {
                k: np.asarray(v)
                for k, v in obs_metrics.metrics_to_dict(out["metrics"]).items()
            }
        return d

    @staticmethod
    def _concat_out(parts: list) -> dict:
        """Concatenate per-segment ``_np_out`` dicts along the round axis."""
        first = parts[0]
        if len(parts) == 1:
            return first
        out: dict = {}
        for k, v in first.items():
            if isinstance(v, dict):
                out[k] = {kk: np.concatenate([p[k][kk] for p in parts])
                          for kk in v}
            else:
                out[k] = np.concatenate([p[k] for p in parts])
        return out

    def _result_from_out(self, o: dict) -> SimResult:
        return SimResult(
            name=self.name,
            energy=np.asarray(o["energy"], np.float64),
            accuracy=np.asarray(o["accuracy"], np.float64),
            loss=np.asarray(o["loss"], np.float64),
            n_scheduled=np.asarray(o["n_scheduled"]),
            q_levels=np.asarray(o["q_levels"]),
            latency=np.asarray(o["latency"], np.float64),
            payload_bits=np.asarray(o["payload_bits"], np.float64),
            rates=np.asarray(o["rates"], np.float64),
            lambda1=np.asarray(o["lambda1"], np.float64),
            lambda2=np.asarray(o["lambda2"], np.float64),
            metrics=(dict(o["metrics"]) if "metrics" in o else None),
        )

    def _write_run_ledger(self, entry: str, n_rounds: int, res: SimResult,
                          run_s: float) -> None:
        if not self.ledger.enabled:
            return
        self._ledger_header(entry, n_rounds)
        for n in range(n_rounds):
            self.ledger.round_row(n, **self._ledger_row(res, n))
        self.ledger.timing("run", run_s, entry=entry, rounds=int(n_rounds))

    def run_compiled(self, n_rounds: int, with_eval: bool = True,
                     segment: Optional[int] = None,
                     ckpt_dir: Optional[str] = None) -> SimResult:
        """The one-scan path: every round traced into one jitted scan
        (every policy mode except "host-ga").

        ``segment=k`` runs the experiment as ceil(n/k) k-round scan
        segments instead (same compiled body, same keys — the trajectory is
        bit-for-bit the unsegmented scan's); with ``ckpt_dir`` the full
        carry + rounds-so-far checkpoint through ``repro.ckpt`` at every
        interior segment boundary, and :meth:`resume_compiled` restarts a
        crashed run from the latest checkpoint mid-experiment.
        """
        assert self.policy_mode != "host-ga", (
            "host-ga decides on the host per round; use run() / run_host_policy"
        )
        if segment is not None:
            assert segment >= 1, segment
            return self._run_segments(n_rounds, with_eval, int(segment),
                                      ckpt_dir)
        if ckpt_dir is not None:
            raise ValueError("ckpt_dir requires segment=k (segmented scan)")
        run = self._next_run()
        with span("sim.run", run=run) as whole:
            with span("sim.dispatch", run=run):
                fn = self._jitted_scan(with_eval)
                keys, ridx = self._scan_xs(n_rounds)
                (flat, *_rest), out = fn(self.data(), self._dyn,
                                         self._init_carry(), keys, ridx)
            with span("sim.device_wait", run=run):
                jax.block_until_ready(out["energy"])
            with span("sim.fetch", run=run):
                res = self._result_from_out(self._np_out(out))
        self.final_flat = flat
        self._write_run_ledger("run_compiled", n_rounds, res, whole.seconds)
        return res

    def _next_run(self) -> int:
        """The ``run`` id the host spans of the next experiment carry."""
        self._runs += 1
        return self._runs

    def _run_segments(self, n_rounds: int, with_eval: bool, segment: int,
                      ckpt_dir: Optional[str], *, start: int = 0,
                      carry=None, parts: Optional[list] = None,
                      entry: str = "run_compiled") -> SimResult:
        """k-round scan segments over the SAME xs schedule as the one-shot
        scan: the full n_rounds key split is sliced per segment and the
        carry threads through unchanged, so the trajectory is bit-for-bit
        the unsegmented scan's (each distinct segment length compiles
        once — at most two: k and the remainder)."""
        from repro import ckpt as repro_ckpt

        run = self._next_run()
        with span("sim.run", run=run) as whole:
            with span("sim.dispatch", run=run):
                fn = self._jitted_scan(with_eval)
                keys, ridx = self._scan_xs(n_rounds)
                carry = self._init_carry() if carry is None else carry
            parts = [] if parts is None else list(parts)
            for b in range(start, n_rounds, segment):
                e = min(b + segment, n_rounds)
                with span("sim.dispatch", run=run):
                    carry, out = fn(self.data(), self._dyn, carry,
                                    keys[b:e], ridx[b:e])
                with span("sim.device_wait", run=run):
                    jax.block_until_ready(out["energy"])
                with span("sim.fetch", run=run):
                    parts.append(self._np_out(out))
                if ckpt_dir is not None and e < n_rounds:
                    tree = {
                        "carry": {f"c{i:02d}": np.asarray(leaf)
                                  for i, leaf in enumerate(carry)},
                        "out": self._concat_out(parts),
                    }
                    repro_ckpt.save_checkpoint(ckpt_dir, e, tree, extra={
                        "kind": "sim_segment", "next_round": int(e),
                        "n_rounds": int(n_rounds), "segment": int(segment),
                        "with_eval": bool(with_eval), "seed": self.seed,
                        "dyn_hash": obs_ledger.pytree_hash(self._dyn),
                        "sim_name": self.name,
                    })
                    if self.ledger.enabled:
                        self.ledger.write("resume", step=int(e),
                                          action="save", dir=str(ckpt_dir))
            with span("sim.fetch", run=run):
                res = self._result_from_out(self._concat_out(parts))
        self.final_flat = carry[0]
        self._write_run_ledger(entry, n_rounds, res, whole.seconds)
        return res

    def resume_compiled(self, ckpt_dir: str) -> SimResult:
        """Restart a segmented :meth:`run_compiled` from its latest
        checkpoint: validates the checkpoint against this sim (seed +
        dynamic-leaf hash + carry arity), restores the scan carry and the
        rounds already run, and finishes the remaining segments on the same
        key schedule — the returned trajectories are bit-for-bit the
        unsegmented scan's (gated in tests/test_sim_faults.py)."""
        from repro import ckpt as repro_ckpt

        tree, meta = repro_ckpt.load_checkpoint(ckpt_dir)
        if meta.get("kind") != "sim_segment":
            raise repro_ckpt.CheckpointError(
                f"{ckpt_dir!r} holds a {meta.get('kind') or 'non-sim'} "
                "checkpoint, not a segmented-scan one"
            )
        if int(meta["seed"]) != self.seed:
            raise repro_ckpt.CheckpointError(
                f"checkpoint seed {meta['seed']} != sim seed {self.seed}"
            )
        dyn_hash = obs_ledger.pytree_hash(self._dyn)
        if meta.get("dyn_hash") != dyn_hash:
            raise repro_ckpt.CheckpointError(
                "checkpoint was taken under different dynamic scenario "
                f"leaves (hash {meta.get('dyn_hash')} != {dyn_hash})"
            )
        carry_d = tree["carry"]
        carry = tuple(jnp.asarray(carry_d[k]) for k in sorted(carry_d))
        n_ref = len(self._init_carry())
        if len(carry) != n_ref:
            raise repro_ckpt.CheckpointError(
                f"carry has {len(carry)} slots, this sim needs {n_ref} "
                "(the downlink/faults gates must match the checkpointing sim)"
            )
        if self.ledger.enabled:
            self.ledger.write("resume", step=int(meta["next_round"]),
                              action="load", dir=str(ckpt_dir))
        return self._run_segments(
            int(meta["n_rounds"]), bool(meta["with_eval"]),
            int(meta["segment"]), ckpt_dir,
            start=int(meta["next_round"]), carry=carry,
            parts=[tree["out"]], entry="resume_compiled",
        )

    # ------------------------------------------------------------- ledger

    def _ledger_header(self, entry: str, n_rounds: int) -> None:
        """One self-describing run header per run: scenario fingerprint,
        fleet shape, policy, telemetry gate (git rev + jax version are
        stamped by the ledger itself)."""
        self.ledger.run_header(
            self.name, entry,
            scenario_hash=obs_ledger.pytree_hash(self._dyn),
            policy=self.policy_mode,
            u=int(self.fleet.n_clients),
            c=int(self.channel.params.n_channels),
            z=int(self.z), rounds=int(n_rounds), seed=self.seed,
            telemetry=self.metrics_cfg.enabled,
            downlink=self.downlink.mode,
        )

    def _ledger_row(self, res: SimResult, n: int) -> dict:
        """Round n of a SimResult -> ledger round-row fields (the
        RoundRecord columns plus the telemetry taps when present)."""
        row = dict(
            energy=float(res.energy[n]), accuracy=float(res.accuracy[n]),
            loss=float(res.loss[n]), n_scheduled=int(res.n_scheduled[n]),
            latency=float(res.latency[n]),
            payload_bits=float(res.payload_bits[n]),
            lambda1=float(res.lambda1[n]), lambda2=float(res.lambda2[n]),
        )
        if res.metrics is not None:
            row.update({k: float(v[n]) for k, v in res.metrics.items()})
        return row

    def make_host_ga_policy(self) -> "search.HostGAPolicy":
        """The host GA controller paired to this sim's constants and
        ``ga_config`` — the oracle that replays a compiled-GA scan."""
        return search.HostGAPolicy(
            self.sysp, self.eps1, self.eps2, self.v_weight,
            cfg=self.ga_config, q_cap=self.q_cap, hetero=self.hetero,
        )

    def make_host_policy(self):
        """The host-side Policy mirroring this sim's compiled controller on
        the shared key schedule — the oracle ``run_host_policy`` replays in
        the per-policy parity suites (tests/test_sim_baselines.py)."""
        from repro.fl import baselines as fl_baselines

        mode = self.policy_mode
        if mode == "greedy":
            return fast_policy.HostFastPolicy(
                self.sysp, self.eps1, self.eps2, self.v_weight,
                q_cap=self.q_cap, hetero=self.hetero,
            )
        if mode in ("compiled-ga", "host-ga"):
            return self.make_host_ga_policy()
        if mode == "no_quant":
            return fl_baselines.NoQuantPolicy(self.sysp)
        if mode == "channel_allocate":
            return fl_baselines.ChannelAllocatePolicy(self.sysp)
        if mode == "principle":
            return fl_baselines.PrinciplePolicy(self.sysp)
        assert mode == "same_size", mode
        return fl_baselines.SameSizePolicy(self.make_host_ga_policy())

    def run(self, n_rounds: int, with_eval: bool = True) -> ExperimentResult:
        """Mode dispatch: one-scan for greedy/compiled-ga, the per-round
        fallback engine with the host GA controller for host-ga. Always
        returns an ``ExperimentResult`` (SimResult adapts via to_result)."""
        if self.policy_mode == "host-ga":
            return self.run_host_policy(
                self.make_host_ga_policy(), n_rounds, channel="sim",
                with_eval=with_eval,
            )
        return self.run_compiled(n_rounds, with_eval=with_eval).to_result()

    # ------------------------------------------------- host-policy fallback

    def _exec_fn(self, with_eval: bool = True):
        """One compiled round execution for externally supplied decisions.

        Takes the decision pre-compacted to the slot axis (``slots`` from
        ``policy.compact_slots_host`` plus per-slot q and eq.-2 weights) and
        replays ``_round_body``'s gather -> SGD -> quantize -> aggregate
        exactly, so a host policy mirroring the compiled one reproduces the
        scan bit for bit. All returned observations are per slot.

        With the quant_mse tap on (telemetry), a trailing per-round MSE is
        returned — the same ops on the same wire values as the scan's tap,
        so the replayed metric matches the compiled one bit for bit.

        With the downlink on, the quantized broadcast is applied on the
        same folded round key as the scan (``DOWNLINK_KEY_TAG``) and the
        realized next-round bound term (plus the dl MSE when tapped) ride
        the return tuple, so ``run_host_policy`` can feed the policy the
        identical ``dl_term`` stream.

        With faults on, ``wd_slot`` carries the per-slot DATA SIZES instead
        of the eq.-2 weights (the weights renormalize over the screened
        survivors *inside*, with the scan's own f32 arithmetic), the extra
        ``v_slot``/``f_slot``/``out_state`` inputs feed the screen, and the
        per-slot verdict + new outage state + fault counters ride the
        return tuple — the replayed draws and screens are bit-for-bit the
        scan's (same ``fault_keys`` fold, same ``screen_slots`` ops).
        """
        tap_mse = self.metrics_cfg.enabled and self.metrics_cfg.quant_mse
        dl_on = self.downlink.enabled
        faults_on = self.faults.enabled
        fv = self._dyn.get("faults")
        u = self.fleet.n_clients

        @jax.jit
        def exec_round(data, flat, slots, q_slot, wd_slot, key,
                       v_slot=None, f_slot=None, out_state=None):
            # identical key discipline to _round_body (k_ch unused: the
            # caller already drew the rates)
            _k_ch, k_batch, k_quant = jax.random.split(key, 3)
            if faults_on:
                k_out, k_fade, k_corr, k_burst = fault_keys(key)
                down_u = draw_outage(k_out, out_state, fv)
                fade_hit_u, fade_mult_u = draw_fade(k_fade, u, fv)
            params = self.unravel(flat)
            x_s, y_s, n_s = gather_active(
                self._traced_fleet(data), slots, mesh=self._mesh,
                client_axes=self._client_axes)
            stacked, g_obs, s_obs = fleet_local_sgd(
                self.loss_fn, self.sysp.tau, self.batch_size, params,
                x_s, y_s, n_s, self.lr, k_batch,
            )
            flat_s = jax.vmap(lambda p: ravel_pytree(p)[0])(stacked)
            if faults_on:
                flat_s = inject_burst(k_burst, slots, flat_s, fv)
            idx, signs, theta = _quantize_wire(
                k_quant, flat_s, q_slot, self.q_cap, self._zpad
            )
            if faults_on:
                d_slot = wd_slot
                idx, signs = corrupt_planes(k_corr, idx, signs, fv)
                ok, n_dropped, n_timeout_real, n_screened = screen_slots(
                    slots, q_slot, d_slot, v_slot, f_slot, theta, idx,
                    signs, down_u, fade_mult_u, fade_hit_u, self.sysp,
                    self.z,
                )
                theta_c = jnp.where(ok, theta, 0.0)
                flat_s = jnp.where(ok[:, None], flat_s, 0.0)
                d_eff = d_slot * ok.astype(jnp.float32)
                d_n = jnp.sum(d_eff)
                w_slot = d_eff / jnp.maximum(d_n, 1e-12)
                agg = self._aggregate(idx, signs, theta_c, w_slot, q_slot)
                new_flat = jnp.where(d_n > 0, agg[: self.z], flat)
                any_payload = d_n > 0
            else:
                w_slot = wd_slot
                agg = self._aggregate(idx, signs, theta, w_slot, q_slot)
                new_flat = jnp.where(jnp.sum(w_slot) > 0, agg[: self.z],
                                     flat)
                any_payload = jnp.sum(w_slot) > 0
            if dl_on:
                exact_flat = new_flat
                new_flat, dl_next = self._downlink_apply(key, new_flat, flat)
            if with_eval:
                acc, loss = self.eval_fn(new_flat, data["test"])
            else:
                acc, loss = jnp.float32(0.0), jnp.float32(0.0)
            out = (new_flat, g_obs, s_obs, theta, acc, loss)
            if faults_on:
                out = out + (ok, down_u.astype(jnp.float32), n_dropped,
                             n_timeout_real, n_screened)
            if tap_mse:
                exact = jnp.einsum("s,sz->z", w_slot, flat_s)
                mse = jnp.sum((agg[: self.z] - exact) ** 2) / self.z
                out = out + (jnp.where(any_payload, mse,
                                       jnp.float32(float("nan"))),)
            if dl_on:
                out = out + (dl_next,)
                if tap_mse:
                    out = out + (jnp.sum((new_flat - exact_flat) ** 2)
                                 / self.z,)
            return out

        return exec_round

    def run_host_policy(self, policy, n_rounds: int,
                        channel: str = "sim",
                        with_eval: bool = True) -> ExperimentResult:
        """Per-round Python fallback: a host Policy (e.g. the GA-backed
        ``QCCFController`` via ``repro.fl.baselines.QCCFPolicy``) makes the
        decisions; training/quantize/aggregate still run compiled.

        ``channel="sim"`` draws rates from the jnp channel on the SAME key
        schedule as ``run_compiled`` — a host policy that mirrors the
        compiled fast path then reproduces the scan decision-for-decision.
        ``channel="host"`` uses the paired numpy ``ChannelModel`` stream
        instead (what ``FLExperiment`` would see).

        The wire format is sized for ``q_cap`` levels, so decisions above it
        are clamped to ``q_cap`` for execution and in the records (build the
        sim with ``q_cap=16`` for baselines that quantize up to 16 bits).
        """
        assert channel in ("sim", "host")
        if channel == "host":
            assert self.host_channel is not None, "build with a host ChannelModel"
        with span("sim.run", run=self._next_run()) as whole:
            records, host_metrics = self._host_rounds(policy, n_rounds,
                                                      channel, with_eval)
        mcfg = self.metrics_cfg
        result = ExperimentResult(getattr(policy, "name", "host_policy"),
                                  records)
        if self.ledger.enabled:
            self._ledger_header("run_host_policy", n_rounds)
            for n, rec in enumerate(records):
                row = dict(
                    energy=rec.energy, accuracy=rec.accuracy, loss=rec.loss,
                    n_scheduled=rec.n_scheduled, latency=rec.latency,
                    payload_bits=rec.payload_bits,
                )
                if mcfg.enabled:
                    row.update(host_metrics[n])
                self.ledger.round_row(n, **row)
            self.ledger.timing("run", whole.seconds, entry="run_host_policy",
                               rounds=int(n_rounds))
        return result

    def _host_rounds(self, policy, n_rounds: int, channel: str,
                     with_eval: bool) -> tuple[list, list]:
        """The rounds of :meth:`run_host_policy`: ``(records, per-round
        telemetry rows)``."""
        exec_round = self._exec_fn(with_eval)
        data = self.data()
        mcfg = self.metrics_cfg
        tap_mse = mcfg.enabled and mcfg.quant_mse
        dl_on = self.downlink.enabled
        # previous round's realized downlink bound term (0.0 before the
        # first broadcast) — same stream the scan threads through its carry
        dl_prev_host = 0.0
        dl_bits_host = (float(core_quant.payload_bits(self.z,
                                                      self.downlink.q_bits))
                        if dl_on else None)
        faults_on = self.faults.enabled
        u = self.fleet.n_clients
        # Markov outage state threaded between exec_round calls (the scan's
        # trailing carry slot); realized Lyapunov terms mirror the scan's
        # hetero/downlink routing (QCCF modes only, see _round_body)
        out_state_h = jnp.zeros((u,), jnp.float32) if faults_on else None
        use_ctx_terms = self.policy_mode in ("greedy", "compiled-ga",
                                             "host-ga")
        consts = self.sysp.bound_constants()
        d_sizes = self.fleet.d_sizes.astype(np.float64)
        g_sq = np.ones(u)
        sigma_sq = np.ones(u)
        theta_max = np.ones(u)
        keys = jax.random.split(jax.random.PRNGKey(self.seed + 1), n_rounds)
        flat = self.flat0
        records: list[RoundRecord] = []
        # per-round telemetry rows of this replay (same schema as the
        # compiled taps; kept for the parity suite and the ledger)
        host_metrics: list[dict] = []
        cum = 0.0
        for n in range(n_rounds):
            if channel == "sim":
                k_ch = jax.random.split(keys[n], 3)[0]
                rates = np.asarray(self.channel.draw_rates(k_ch), np.float64)
            else:
                rates = self.host_channel.draw_rates()
            ctx = RoundContext(
                rates=rates,
                d_sizes=d_sizes,
                g_sq=g_sq / max(float(np.mean(g_sq)), 1e-12),
                sigma_sq=sigma_sq / max(float(np.mean(sigma_sq)), 1e-12),
                theta_max=theta_max.copy(),
                z=self.z,
            )
            if hasattr(policy, "set_round_key"):
                # same per-round GA key derivation as the compiled-ga scan
                policy.set_round_key(jax.random.fold_in(keys[n], search.GA_KEY_TAG))
            if dl_on and hasattr(policy, "set_downlink_term"):
                policy.set_downlink_term(dl_prev_host)
            dec = policy.decide(ctx)
            # continuous-q tap: KKT-backed policies attach the clipped
            # q_hat; baselines fall back to their raw pre-clamp level
            q_cont_host = getattr(dec, "q_cont",
                                  np.asarray(dec.q, np.float64).copy())
            # clamp into the wire format: a uint8/uint16 index plane sized
            # for q_cap would silently wrap above it
            q_exec = np.clip(dec.q, 1, self.q_cap) * dec.a
            dec.q = np.where(dec.a > 0, q_exec, dec.q * 0)
            # compacted replay: the same slot derivation as the compiled
            # round body (drop unkept channels, stable channel-order slots)
            assign = np.asarray(dec.assign)
            a_np = np.asarray(dec.a)
            assign_kept = np.where(
                (assign >= 0) & (a_np[np.clip(assign, 0, u - 1)] > 0),
                assign, -1,
            )
            slots = fast_policy.compact_slots_host(assign_kept, u)
            mask = slots >= 0
            cids = np.maximum(slots, 0)
            # the compacted replay trains exactly the slot set; a Policy
            # whose participation vector disagrees with its channel
            # assignment (a client scheduled without a channel, or on two
            # channels) would silently train the wrong set — fail loudly
            sched_from_slots = np.sort(cids[mask])
            sched_from_a = np.flatnonzero(a_np > 0)
            assert np.array_equal(sched_from_slots, sched_from_a), (
                "policy decision inconsistent: participation a="
                f"{sched_from_a.tolist()} vs channel-assigned clients "
                f"{sched_from_slots.tolist()} — every scheduled client "
                "must hold exactly one channel (see policy.compact_slots)"
            )
            # eq.-2 weights in f32, the scan's own arithmetic: sizes are
            # small integers (f32-exact sums), so the f32 division lands on
            # the identical IEEE result — the replayed wire (and the
            # quant_mse tap) stays bit-for-bit the compiled one, with no
            # f64-then-cast double rounding.
            d_slot = np.where(mask, d_sizes[cids], 0.0).astype(np.float32)
            w_slot = d_slot / np.maximum(d_slot.sum(dtype=np.float32),
                                         np.float32(1e-12))
            q_slot = np.where(mask, q_exec[cids], 0)
            v_assigned = np.zeros(u)
            for c, cid in enumerate(dec.assign):
                if cid >= 0:
                    v_assigned[cid] += float(ctx.rates[cid, c])
            fault_kw = {}
            if faults_on:
                # the screen's inputs, compacted like the scan's: assigned
                # rate and KKT frequency per slot (f32 casts of the host
                # decision — the one analog leak in the fault replay; the
                # draws, planes, and weight renormalization are exact)
                fault_kw = dict(
                    v_slot=jnp.asarray(np.where(mask, v_assigned[cids], 0.0),
                                       jnp.float32),
                    f_slot=jnp.asarray(
                        np.where(mask, np.asarray(dec.f)[cids], 0.0),
                        jnp.float32),
                    out_state=out_state_h,
                )
            flat, g_obs, s_obs, theta, acc, loss, *extras = exec_round(
                data, flat, jnp.asarray(slots, jnp.int32),
                jnp.asarray(q_slot, jnp.int32),
                jnp.asarray(d_slot if faults_on else w_slot, jnp.float32),
                keys[n], **fault_kw,
            )
            extras = list(extras)
            ok_h = None
            n_drop_h = n_tmo_h = n_scr_h = None
            if faults_on:
                ok_h = np.asarray(extras.pop(0))
                out_state_h = extras.pop(0)
                n_drop_h = float(extras.pop(0))
                n_tmo_h = float(extras.pop(0))
                n_scr_h = float(extras.pop(0))
            mse_tap = extras.pop(0) if tap_mse else None
            dl_mse_tap = None
            if dl_on:
                dl_next = extras.pop(0)
                if tap_mse:
                    dl_mse_tap = extras.pop(0)
            # only DELIVERED slots feed the estimators (upd == mask when
            # faults are off — the historical path, bit for bit)
            upd = mask if ok_h is None else (mask & ok_h)
            sel = cids[upd]
            g_sq[sel] = 0.7 * g_sq[sel] + 0.3 * np.asarray(g_obs)[upd]
            sigma_sq[sel] = 0.7 * sigma_sq[sel] + 0.3 * np.maximum(
                np.asarray(s_obs)[upd], 1e-8
            )
            theta_max[sel] = np.asarray(theta)[upd]
            planned_dt = float(dec.data_term)
            planned_qt = float(dec.quant_term)
            if faults_on:
                # queue feedback at the REALIZED participation, like the
                # scan (f64 host analog of policy.realized_terms)
                a_real = np.zeros(u)
                a_real[sel] = 1.0
                dt_r, qt_r = bounds.realized_terms(
                    consts, a_real, d_sizes, ctx.g_sq, ctx.sigma_sq,
                    ctx.theta_max, np.maximum(np.asarray(dec.q), 1), self.z,
                    hetero=self.hetero if use_ctx_terms else None,
                    dl_term=(dl_prev_host if (dl_on and use_ctx_terms)
                             else 0.0),
                )
                dec.data_term = dt_r
                dec.quant_term = qt_r
            policy.commit(dec)
            cum += dec.total_energy
            records.append(RoundRecord(
                round=n, energy=dec.total_energy, cum_energy=cum,
                accuracy=float(acc), loss=float(loss),
                n_scheduled=int(dec.a.sum()), q_levels=dec.q.copy(),
                latency=float(dec.latency.max() if dec.a.any() else 0.0),
                payload_bits=float(np.sum(
                    np.where(dec.a > 0, self.z * np.maximum(dec.q, 1)
                             + self.z + 32.0, 0.0))),
                rates=v_assigned,
            ))
            if mcfg.enabled:
                # same-schema replay of the scan's tap: the SAME jitted
                # decision_metrics on the host decision's arrays (see
                # repro.obs.metrics for which fields are exact vs analog);
                # the host loop has no per-generation GA median.
                host_metrics.append(obs_metrics.decision_metrics_host(
                    a_np, np.asarray(dec.q), np.asarray(q_cont_host),
                    np.asarray(dec.f), np.asarray(dec.energy), d_sizes,
                    planned_dt, planned_qt, self.sysp,
                    quant_mse=float(mse_tap) if tap_mse else None,
                    ga_best=getattr(dec, "ga_best", None),
                    dl_payload_bits=dl_bits_host,
                    dl_mse=(float(dl_mse_tap) if dl_mse_tap is not None
                            else None),
                    n_dropped=n_drop_h, n_screened=n_scr_h,
                    n_timeout_real=n_tmo_h,
                ))
            if dl_on:
                # becomes next round's dl_term, as in the scan's carry
                dl_prev_host = float(dl_next)
        self.final_flat = flat
        self.last_host_metrics = host_metrics if mcfg.enabled else None
        return records, host_metrics

    # -------------------------------------------------------------- sharding

    def shard_clients(self, mesh, axis: str = "data") -> None:
        """Distribute the client axis over a mesh axis via the repro.dist
        logical-axis plan: the stacked fleet arrays are annotated with the
        ``clients`` logical name and the plan's rule table resolves it to
        ``axis`` (divisibility-gated); computation follows the data. The
        arrays are jit arguments and are re-placed here; the cached scans
        are dropped because the slot gather and the aggregate kernel are
        traced under ``shard_map`` over ``mesh`` (see :meth:`_aggregate`,
        ``fleet.gather_active``)."""
        from repro.dist import sharding as shd
        from repro.dist.plan import make_plan

        batch = {"x": self.fleet.x, "y": self.fleet.y, "n": self.fleet.n_samples}
        plan = make_plan(mesh, client_axis=axis)
        specs = shd.data_specs(plan, batch, leading="clients")
        named = plan.named(specs)
        placed = {k: jax.device_put(v, named[k]) for k, v in batch.items()}
        self.fleet = dataclasses.replace(
            self.fleet, x=placed["x"], y=placed["y"], n_samples=placed["n"],
        )
        self._mesh = mesh
        self._client_axes = specs["x"][0]
        self._compiled.clear()


# ------------------------------------------------------------------- build

@span("sim.build")
def build_sim(
    task: str = "tiny",
    *,
    scenario: "Optional[Scenario | str]" = None,
    n_clients: int = 64,
    n_channels: Optional[int] = None,
    mu: Optional[float] = None,
    beta: Optional[float] = None,
    v_weight: Optional[float] = None,
    alpha_dirichlet: Optional[float] = None,
    lr: float = 0.05,
    seed: int = 0,
    batch_size: int = 32,
    q_cap: int = 8,
    block_m: int = 64,
    n_test: int = 1024,
    target_q: Optional[float] = None,
    policy_mode: Optional[str] = None,
    ga_config: Optional[GAConfig] = None,
    hetero_weight: Optional[float] = None,
    name: Optional[str] = None,
    telemetry: Optional[MetricsConfig] = None,
    ledger: Optional[obs_ledger.Ledger] = None,
    downlink: "Optional[DownlinkConfig | str]" = None,
    faults: Optional[FaultSpec] = None,
) -> FleetSim:
    """Mirror of ``repro.fl.experiment.build_experiment`` for the compiled
    engine: same task specs, same dataset/draw seeds, same client drop, and
    eps1/eps2 from the same ``auto_epsilons`` probe, so small-scale runs are
    directly comparable with the object-based ``FLExperiment``.

    ``scenario`` selects a whole experiment configuration as data — a
    :class:`repro.sim.scenario.Scenario` or a registered preset name
    (``single_bs``/``cellfree_a4``/``noniid_a01``); explicit kwargs still
    override individual scenario fields. A preset name is sized by
    ``n_clients``/``n_channels``; a Scenario instance carries its own fleet
    shape. ``scenario=None`` (or any ``mode="single_bs"`` topology) keeps
    the legacy numpy ``ChannelModel`` client drop and eps probe, so those
    paths are bit-for-bit the pre-scenario engine; cell-free topologies
    drop via the topology's jax path and probe through the jnp channel.
    """
    from repro.core.controller import auto_epsilons
    from repro.fl.experiment import TASKS, task_data_sizes

    n_channels = n_clients if n_channels is None else n_channels
    if isinstance(scenario, str):
        scenario = get_scenario(scenario, n_clients=n_clients,
                                n_channels=n_channels)
    if scenario is not None:
        n_clients = scenario.channel.n_clients
        n_channels = scenario.channel.n_channels
        mu = scenario.data.mu if mu is None else mu
        beta = scenario.data.beta if beta is None else beta
        if alpha_dirichlet is None:
            alpha_dirichlet = scenario.data.alpha_dirichlet
        v_weight = scenario.lyapunov.v_weight if v_weight is None else v_weight
        target_q = scenario.lyapunov.target_q if target_q is None else target_q
        policy_mode = scenario.policy if policy_mode is None else policy_mode
        if hetero_weight is None:
            hetero_weight = scenario.lyapunov.hetero_weight
        if faults is None:
            faults = scenario.faults
    v_weight = 100.0 if v_weight is None else float(v_weight)
    alpha_dirichlet = 0.5 if alpha_dirichlet is None else float(alpha_dirichlet)
    target_q = 6.0 if target_q is None else float(target_q)
    policy_mode = "greedy" if policy_mode is None else policy_mode
    hetero_weight = 0.0 if hetero_weight is None else float(hetero_weight)

    task_spec, cnn_cfg, sysp = TASKS[task]
    mu, beta = task_data_sizes(task, mu, beta)
    with span("sim.data"):
        img_task = SyntheticImageTask(task_spec, seed=seed)
        sizes = gaussian_sizes(n_clients, mu, beta, seed=seed)
        datasets = make_federated_datasets(img_task, n_clients, sizes,
                                           alpha=alpha_dirichlet, seed=seed)
        test = make_test_set(img_task, n=n_test, seed=seed + 999)
    fleet = build_fleet(datasets)

    loss_fn = functools.partial(cnn.loss_fn, cnn_cfg)
    params = cnn.init_params(cnn_cfg, jax.random.PRNGKey(seed))
    _flat0, unravel = ravel_pytree(params)

    def eval_fn(flat, test_set):
        return cnn.eval_metrics(cnn_cfg, unravel(flat), test_set["x"],
                                test_set["y"])

    with span("sim.channel"):
        ch_params = (scenario.channel if scenario is not None
                     else ChannelParams(n_clients=n_clients,
                                        n_channels=n_channels))
        if scenario is None or scenario.topology.mode == "single_bs":
            # legacy path: numpy drop + numpy probe — bit-for-bit the
            # pre-scenario engine (golden-regressed in tests/test_scenario.py)
            host_channel = ChannelModel(ch_params, seed=seed)
            channel = SimChannel.from_host_model(host_channel)
            if scenario is not None:
                channel = dataclasses.replace(
                    channel, association=scenario.topology.association
                )
            probe_rates = host_channel.draw_rates()
        else:
            host_channel = None
            drop_key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                          DROP_KEY_TAG)
            channel = SimChannel.from_topology(drop_key, ch_params,
                                               scenario.topology)
            probe_key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                           PROBE_KEY_TAG)
            probe_rates = np.asarray(channel.draw_rates(probe_key),
                                     np.float64)

        z = int(_flat0.shape[0])
        probe = RoundContext(
            rates=probe_rates, d_sizes=sizes.astype(np.float64),
            g_sq=np.full(n_clients, 1.0), sigma_sq=np.full(n_clients, 1.0),
            theta_max=np.full(n_clients, 1.0), z=z,
        )
        eps1, eps2 = auto_epsilons(probe, sysp, target_q=target_q)

    hetero = None
    if hetero_weight > 0.0:
        hetero = 1.0 + hetero_weight * hetero_kl(datasets, task_spec.n_classes)

    if name is None:
        name = (f"sim_{scenario.name}_{policy_mode}" if scenario is not None
                else "sim_qccf")
    if isinstance(downlink, str):
        # convenience: "quant"/"delta"/"off" at default q_bits
        downlink = DownlinkConfig(mode=downlink)
    return FleetSim(
        fleet, params, loss_fn, eval_fn, channel, sysp, test_set=test,
        eps1=eps1, eps2=eps2, v_weight=v_weight, lr=lr,
        batch_size=batch_size, q_cap=q_cap,
        block_m=block_m, seed=seed, host_channel=host_channel,
        policy_mode=policy_mode, ga_config=ga_config,
        hetero=hetero, scenario=scenario, name=name,
        telemetry=telemetry, ledger=ledger, downlink=downlink,
        faults=faults,
    )
