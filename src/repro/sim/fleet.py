"""Stacked client fleet: padded per-client datasets + vmapped local SGD.

The object-based runtime (`repro.fl`) holds one ``FLClient`` per client and
dispatches a jitted tau-step SGD per scheduled client per round — a host
loop that tops out around ten clients. Here the whole fleet lives in four
arrays (data, labels, per-client sample counts, sizes) padded to a common
``N_max``; each client's samples are one flat row of ``x`` (the layout a
TPU can slice rows out of without copying the fleet, see :class:`Fleet`);
one ``jax.vmap`` of the *same* SGD scan body
(:func:`repro.fl.client.sgd_scan_body`) trains every client at once, and
per-client minibatch draws happen with ``jax.random`` inside the trace
(indices are drawn in ``[0, n_i)`` so padding rows are never sampled).

Active-set compaction (key-schedule contract)
---------------------------------------------
Per-round work runs on the *scheduled slot axis*, not the fleet axis: the
engine gathers the S = min(U, C) scheduled clients' rows
(:func:`gather_active` on ``FastDecision.slots``), trains only those, and
scatters the G²/σ²/θ observations back (:func:`scatter_slots`). The SGD
batch keys are therefore **per slot, not per client**:
``split(k_batch, S)[s]`` feeds slot ``s`` (the client on channel-order
position ``s``), and the quantizer's uniform draw is shaped ``(S, Zpad)``.
Any replay (``FleetSim.run_host_policy``, numpy oracles) must derive the
same slot vector (``policy.compact_slots_host``) to reproduce the stream
bit for bit — a client's draws depend on its slot position, not its id.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.fl.client import sgd_scan_body
from repro.obs.profile import scope as _profile_scope, span

Pytree = Any
LANES = 128


@dataclasses.dataclass(frozen=True)
class Fleet:
    """All U client datasets as stacked, padded arrays.

    ``x`` holds each client's padded samples as ONE row, zero-padded to a
    multiple of 128 lanes. On a TPU that is the layout the device picks
    for it (row-major; an unaligned row length makes the client axis the
    minor one), so :func:`gather_active` slices S rows without copying
    the fleet. A rank-5 ``(U, N_max, H, W, C)`` array, or a plain row
    gather, made the compiler add whole-fleet copies in other layouts:
    over 19 GB for the 16 GB of one chip at FEMNIST U = 1024.
    :func:`gather_active` restores the sample shape on the gathered rows.
    """

    x: jax.Array          # (U, L) fp32, L = N_max*H*W*C rounded up to 128
    y: jax.Array          # (U, N_max) int32
    n_samples: jax.Array  # (U,) int32 true per-client sizes (mask)
    d_sizes: np.ndarray   # host copy of n_samples for setup-time math
    sample_shape: tuple   # (H, W, C) of one sample

    @property
    def n_clients(self) -> int:
        return int(self.x.shape[0])

    @property
    def n_max(self) -> int:
        return int(self.y.shape[1])


def build_fleet(datasets: list[dict]) -> Fleet:
    """Stack ``repro.data.synthetic.make_federated_datasets`` output.

    Clients are padded to the largest local dataset; ``n_samples`` masks the
    padding (batch indices are drawn modulo the true size, so padded rows
    are dead weight, never training signal).
    """
    sizes = np.array([d["x"].shape[0] for d in datasets], dtype=np.int64)
    n_max = int(sizes.max())
    u = len(datasets)
    sample_shape = tuple(datasets[0]["x"].shape[1:])
    row = n_max * math.prod(sample_shape)
    with span("sim.fleet_stack"):
        xs = np.zeros((u, -(-row // LANES) * LANES), np.float32)
        ys = np.zeros((u, n_max), np.int32)
        for i, d in enumerate(datasets):
            xs[i, : d["x"].size] = d["x"].reshape(-1)
            ys[i, : sizes[i]] = d["y"]
    with span("sim.fleet_place"):
        x, y, n = jax.block_until_ready(
            (jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(sizes, jnp.int32)))
    return Fleet(x=x, y=y, n_samples=n, d_sizes=sizes,
                 sample_shape=sample_shape)


def _slice_rows(x: jax.Array, rows: jax.Array) -> jax.Array:
    """``x[rows]`` as one dynamic row slice per entry: XLA:TPU lowers a
    row gather on the fleet by slicing the WHOLE fleet into temporaries
    (gigabytes at FEMNIST U = 1024); the slices touch only the rows."""
    return jax.lax.map(
        lambda r: jax.lax.dynamic_index_in_dim(x, r, 0, keepdims=False), rows)


def gather_active(fleet: Fleet, slots: jax.Array, *, mesh=None,
                  client_axes=None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compact the fleet to the fixed-width scheduled-slot axis.

    ``slots`` is the decision's (S,) client-id vector (-1 padded); padding
    slots gather client 0's rows — their outputs are masked out downstream
    (zero aggregation weight, masked scatter), so they are dead weight only.
    Returns ``(x_s, y_s, n_s)`` with leading axis S; ``x_s`` is
    ``(S, N_max, H, W, C)``.

    ``client_axes`` names the ``mesh`` axes the fleet's rows are sharded
    over (``FleetSim.shard_clients``): each device then slices the slots
    it holds (zeros for the rest) and one psum of the S rows assembles
    them — a dynamic slice across shards would all-gather the fleet.
    """
    cid = jnp.maximum(slots, 0)
    if client_axes is None:
        x_s = _slice_rows(fleet.x, cid)
    else:
        def local_rows(x, c):
            n = x.shape[0]
            lo = jax.lax.axis_index(client_axes) * n
            mine = (c >= lo) & (c < lo + n)
            rows = _slice_rows(x, jnp.clip(c - lo, 0, n - 1))
            return jax.lax.psum(jnp.where(mine[:, None], rows, 0.0),
                                client_axes)

        x_s = jax.shard_map(
            local_rows, mesh=mesh, in_specs=(P(client_axes), P()),
            out_specs=P(), check_vma=False,
        )(fleet.x, cid)
    row = fleet.n_max * math.prod(fleet.sample_shape)
    return (
        x_s[:, :row].reshape((x_s.shape[0], fleet.n_max) + fleet.sample_shape),
        jnp.take(fleet.y, cid, axis=0),
        jnp.take(fleet.n_samples, cid, axis=0),
    )


def scatter_slots(slots: jax.Array, obs: jax.Array, n_clients: int) -> jax.Array:
    """(S,) per-slot observations -> (U,) per-client, zeros elsewhere.

    Real slots are injective (one channel per client after repair), so a
    masked ``.at[].add`` is an exact scatter; padding slots (-1) are dropped.
    """
    mask = slots >= 0
    cid = jnp.maximum(slots, 0)
    zero = jnp.zeros((n_clients,), obs.dtype)
    return zero.at[cid].add(jnp.where(mask, obs, jnp.zeros_like(obs)))


def fleet_local_sgd(
    loss_fn: Callable,
    tau: int,
    batch_size: int,
    params: Pytree,
    fleet_x: jax.Array,
    fleet_y: jax.Array,
    n_samples: jax.Array,
    lr: float,
    key: jax.Array,
) -> tuple[Pytree, jax.Array, jax.Array]:
    """tau local SGD steps for every gathered client at once (Fig. 1 step 3).

    The leading axis is whatever the caller hands in — the full fleet (U)
    or, on the engine's hot path, the compacted active set (S slots from
    :func:`gather_active`). ``key`` splits once per leading-axis row, which
    is the per-slot key schedule documented in the module docstring.

    Returns ``(stacked_params, g_mean, g_var)`` with that same leading axis
    on every params leaf; ``g_mean``/``g_var`` are the per-client G_i^2 and
    sigma_i^2 observations that feed the controller's EMA estimators.
    """
    step = sgd_scan_body(loss_fn, lr)
    u = fleet_x.shape[0]

    def one_client(x, y, n, k):
        idx = jax.random.randint(k, (tau, batch_size), 0, n)
        batches = {"x": x[idx], "y": y[idx]}
        (p, gsq_acc), (_losses, gsqs) = jax.lax.scan(step, (params, 0.0), batches)
        return p, gsq_acc / tau, jnp.var(gsqs)

    keys = jax.random.split(key, u)
    with _profile_scope("fleet_local_sgd"):
        return jax.vmap(one_client)(fleet_x, fleet_y, n_samples, keys)


def ema_update(
    ema: jax.Array, obs: jax.Array, a: jax.Array, decay: float = 0.7,
    floor: float = 0.0,
) -> jax.Array:
    """Masked EMA: scheduled clients blend in the new observation, others
    keep their state (mirrors ``FLExperiment``'s 0.7/0.3 estimators)."""
    blended = decay * ema + (1.0 - decay) * jnp.maximum(obs, floor)
    return jnp.where(a > 0, blended, ema)
