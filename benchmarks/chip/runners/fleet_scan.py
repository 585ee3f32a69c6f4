"""Runner of the compiled QCCF fleet scan (``repro.sim``), the program of
the paper's CNN configurations.

An experiment is one ``FleetSim.run_compiled(rounds, with_eval=...)``
call, which returns its per-round outputs on the host. Every experiment
of a run uses the same keys, so each must repeat the warm-up's outputs
bit for bit and pass the structural checks. After the window the plain
reference (``chipbench.reference``) follows the first rounds of the last
experiment from the seed alone, and ``chipbench.checks`` compares them.
"""
from __future__ import annotations

import sys

import numpy as np

from chipbench import checks, flops, reference

NUMBERS = checks.NUMBERS
MAX_SEED_DRAWS = 100_000


def check_config(cfg: dict) -> None:
    """The model is stored in float32 at a dot precision the reference
    can run, or the configuration is not one this runner runs."""
    prec = cfg["precision"]
    if prec["storage"] != "float32" or prec["dot"] not in \
            reference.DOT_PRECISION:
        raise ValueError(f"the fleet scan runs float32 storage at a dot "
                         f"precision of {sorted(reference.DOT_PRECISION)}; "
                         f"the configuration states {prec}")


def program_seed(cfg: dict, seed: int) -> int:
    """The seed the program and the reference are given for ``--seed``:
    the first of a stream of candidates drawn from ``--seed`` whose
    fleet's largest client holds exactly ``data.largest_client`` samples.
    The stacked fleet is padded to its largest client, so this gives
    every seed the same fleet shape and bytes: the same compiled scan
    (found in the persistent cache) and the same work per round."""
    want = cfg["data"]["largest_client"]
    rng = np.random.default_rng(seed % 2**64)
    for _ in range(MAX_SEED_DRAWS):
        cand = int(rng.integers(1, 2**31 - 1))
        if reference.client_sizes(cfg, cand).max() == want:
            return cand
    raise ValueError(f"no fleet with largest client {want} in "
                     f"{MAX_SEED_DRAWS} draws from seed {seed}")


def _sim_kwargs(cell, seed: int) -> dict:
    """``repro.sim.build_sim``'s arguments for the cell at a program seed."""
    from repro.core.genetic import GAConfig

    cfg, tr = cell.config, cell.traffic
    d, t, ly = cfg["data"], cfg["training"], cfg["lyapunov"]
    kw = dict(
        scenario=cfg["scenario"], n_clients=cfg["n_clients"],
        n_channels=cfg["n_channels"], mu=d["mu"], beta=d["beta"],
        alpha_dirichlet=d["alpha_dirichlet"], n_test=d["n_test"],
        v_weight=ly["v_weight"], target_q=ly["target_q"], q_cap=ly["q_cap"],
        lr=t["lr"], batch_size=t["batch_size"], block_m=cfg["block_m"],
        seed=seed, policy_mode=tr["policy"])
    if tr.get("ga"):
        kw["ga_config"] = GAConfig(**tr["ga"])
    return kw


def _check_stated(sim, cfg: dict) -> None:
    """The program runs what the configuration states, or no result."""
    stated = {k: cfg["system"][k] for k in cfg["system"]}
    have = {k: getattr(sim.sysp, k) for k in stated}
    if have != stated or sim.z != cfg["model"]["z"] \
            or sim.sysp.tau != cfg["training"]["tau"]:
        raise ValueError(f"program runs z={sim.z} {have}, the configuration "
                         f"states z={cfg['model']['z']} {stated}")


def useful_flops(cfg: dict, traffic: dict, n_scheduled) -> int:
    """Useful FLOPs of rounds that scheduled ``n_scheduled`` clients each
    (``flops.round_useful_flops``, eval included where the traffic runs
    it). The count is linear in the scheduled clients and the eval
    samples, so one call over the sums is the sum over the rounds."""
    t = cfg["training"]
    n = np.asarray(n_scheduled, dtype=np.int64)
    n_test = cfg["data"]["n_test"] if traffic["with_eval"] else 0
    return flops.round_useful_flops(cfg["model"], t["tau"], t["batch_size"],
                                    int(n.sum()), n_test * n.size)


class FleetScan:
    """The fleet of the cell at the program seed of ``seed``, built on the
    device, the configuration's constants checked against the program's."""

    def __init__(self, cell, seed: int) -> None:
        import jax

        from repro.sim import build_sim

        cfg, tr = cell.config, cell.traffic
        self.seed = program_seed(cfg, seed)
        print(f"program seed {self.seed}", file=sys.stderr)
        self.rounds = tr["rounds_per_experiment"]
        self._with_eval = tr["with_eval"]
        self._cfg, self._traffic = cfg, tr
        self._sim = build_sim(cfg["task"], **_sim_kwargs(cell, self.seed))
        jax.block_until_ready(self._sim.data())
        _check_stated(self._sim, cfg)

    def compile(self):
        return self._sim.lower(self.rounds,
                               with_eval=self._with_eval).compile()

    def experiment(self):
        return self._sim.run_compiled(self.rounds, with_eval=self._with_eval)

    def faults(self, res, warm) -> list:
        cfg = self._cfg
        bad = checks.structural_faults(res, self.rounds, cfg["n_clients"],
                                       cfg["n_channels"],
                                       cfg["lyapunov"]["q_cap"])
        if not checks.same_result(res, warm):
            bad.append("outputs differ from the warm-up's")
        return bad

    def useful_flops(self, res) -> int:
        return useful_flops(self._cfg, self._traffic, res.n_scheduled)

    def outputs(self, res) -> dict:
        return {k: np.asarray(getattr(res, k)) for k in checks.OUTPUTS}


def build(cell, seed: int) -> FleetScan:
    return FleetScan(cell, seed)


def check(cell, seed: int, outputs: dict) -> dict:
    """The reference follows the first ``reference_rounds`` rounds from
    the program seed alone; in the genetic search's cell it judges the
    program's reported decisions (``follow``)."""
    cfg, tr = cell.config, cell.traffic
    follow = outputs if tr["policy"] == "compiled-ga" else None
    ref = reference.Reference(cfg, tr, program_seed(cfg, seed),
                              follow=follow).run(tr["reference_rounds"])
    return checks.compare(outputs, ref)
