"""What decides ``correct`` in the fleet scan's cells
(``runners/fleet_scan.py``): the program's outputs against the plain
reference (``chipbench.reference``), number by number, each against the
cell's limit; and the structural checks every experiment of a window
must pass."""
from __future__ import annotations

import numpy as np

from chipbench.reference import RATE_MATCH

# an experiment's per-round outputs that are checked and compared
OUTPUTS = ("energy", "accuracy", "loss", "n_scheduled", "q_levels", "rates",
           "lambda1", "lambda2")
# the numbers compared, in the order they are printed
NUMBERS = ("decision_mismatch", "energy_gap", "queue_gap", "loss_gap")


def compare(prog: dict, ref: dict) -> dict:
    """Numbers over the rounds the reference followed.

    decision_mismatch  (round, client) pairs whose schedule, level or
                       channel (assigned rate off by more than 1%) differ,
                       and scheduled clients whose rate matches no channel
    energy_gap         largest relative gap of a round's total energy
    queue_gap          largest relative gap of lambda1 / lambda2 (relative
                       to the larger of the reference's queue and its budget)
    loss_gap           largest relative gap of the global model's test loss
    """
    n = len(ref["energy"])
    p = {k: np.asarray(prog[k])[:n] for k in (
        "energy", "loss", "q_levels", "rates", "lambda1", "lambda2")}
    q_bad = p["q_levels"] != ref["q_levels"]
    v_p, v_r = p["rates"], ref["rates"]
    v_bad = np.abs(v_p - v_r) > RATE_MATCH * np.maximum(np.abs(v_p), np.abs(v_r))
    e_p, e_r = p["energy"], ref["energy"]
    with np.errstate(divide="ignore", invalid="ignore"):
        e_gap = np.where(np.maximum(e_p, e_r) > 0,
                         np.abs(e_p - e_r) / np.maximum(np.abs(e_r), 1e-30),
                         0.0)
    eps1, eps2 = ref["eps"]
    q_gap = np.maximum(
        np.abs(p["lambda1"] - ref["lambda1"])
        / np.maximum(np.abs(ref["lambda1"]), eps1),
        np.abs(p["lambda2"] - ref["lambda2"])
        / np.maximum(np.abs(ref["lambda2"]), eps2))
    return {
        "decision_mismatch": float(np.sum(q_bad | v_bad)
                                   + np.sum(ref["unmatched"])),
        "energy_gap": float(np.max(e_gap)),
        "queue_gap": float(np.max(q_gap)),
        "loss_gap": float(np.max(np.abs(p["loss"] - ref["loss"])
                                 / np.abs(ref["loss"]))),
    }


def structural_faults(res, n_rounds: int, u: int, c: int, q_cap: int) -> list:
    """What one experiment's outputs must satisfy whatever the seed:
    shapes, finite non-negative energy, accuracy in [0, 1], positive
    eval loss, at most C scheduled, energy spent exactly in rounds with a
    schedule, levels in [1, q_cap] exactly for the scheduled, rates
    exactly for the scheduled, finite queues."""
    out = []

    def need(cond, what):
        if not cond:
            out.append(what)

    need(res.energy.shape == (n_rounds,), f"energy shape {res.energy.shape}")
    need(res.q_levels.shape == (n_rounds, u), f"q shape {res.q_levels.shape}")
    if out:
        return out
    need(np.all(np.isfinite(res.energy)) and np.all(res.energy >= 0),
         "energy not finite and non-negative")
    need(np.all(np.isfinite(res.loss)) and np.all(res.loss > 0),
         "eval loss not finite and positive")
    need(np.all((res.accuracy >= 0) & (res.accuracy <= 1)),
         "accuracy outside [0, 1]")
    need(np.all((res.n_scheduled >= 0) & (res.n_scheduled <= c)),
         f"n_scheduled outside [0, {c}]")
    need(np.array_equal(res.energy > 0, res.n_scheduled > 0),
         "energy and schedule disagree")
    sched = res.q_levels > 0
    need(np.array_equal(sched.sum(axis=1), res.n_scheduled),
         "q_levels and n_scheduled disagree")
    q = res.q_levels[sched]
    need(np.all((q >= 1) & (q <= q_cap)), f"q outside [1, {q_cap}]")
    need(np.all(res.rates[sched] > 0) and np.all(res.rates[~sched] == 0),
         "assigned rates do not match the schedule")
    need(np.all(np.isfinite(res.lambda1)) and np.all(np.isfinite(res.lambda2)),
         "Lyapunov queues not finite")
    return out


def same_result(a, b) -> bool:
    """Bit-for-bit equality of two experiments' outputs."""
    return all(np.array_equal(getattr(a, f), getattr(b, f)) for f in OUTPUTS)
