import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# a dry run compiles for 512 host devices: pin the CPU platform, so on a
# machine with an accelerator the mesh is not built from its chips (and
# this process never holds one)
os.environ["JAX_PLATFORMS"] = "cpu"
# NOTE: the lines above MUST run before any other import (jax locks the
# device count and platform at first init). Do not move them.

"""Multi-pod dry-run driver.

For one (arch x input-shape x mesh) combination:
  lower + compile the canonical step (train_step for train shapes,
  prefill/serve_step for inference shapes), print memory_analysis() and
  cost_analysis(), parse the collective ops out of the compiled HLO, and
  emit a JSON record with the three roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_8b \
      --shape train_4k [--multi-pod | --mesh-shape 1x4x2x16] [--fl-round] \
      [--causal-skip] [--out results.json]

``--mesh-shape`` takes a 2D/3D/4D shape mapped onto the trailing axes of
``(pod, data, seq, model)``; a rank-4 shape activates sequence and
expert parallelism through the logical-axis plan. Gates on top of
lower+compile success:

  --require-seq-sharded   fail unless no big per-device intermediate
                          still carries the full sequence length
                          (``hlo_analysis.full_length_intermediates``);
  --require-alltoall      fail unless the compiled HLO contains
                          all-to-all collectives (the MoE expert
                          dispatch on an expert-sharded mesh).

``--wire-ratio`` switches to the pod-scale wire accounting mode: the
federated round is lowered in BOTH wire modes on the multi-pod mesh and
the record carries the per-arch inter-pod byte ratio (uint8 wire / fp32
payload) via ``hlo_analysis.inter_axis_bytes``.

Exit code 0 = lower+compile (and every requested gate) succeeded.
"""
import argparse
import json
import re
import sys
import traceback


# v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link

_COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"(bf16|f32|f16|u32|s32|u8|s8|u16|s16|f64|pred|s64|u64)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "u8": 1, "s8": 1,
    "u16": 2, "s16": 2, "u32": 4, "s32": 4, "s64": 8, "u64": 8, "pred": 1,
}


def _bytes_of_shape(m: re.Match) -> int:
    dtype, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collectives(hlo_text: str) -> dict:
    """Sum *operand* bytes of every collective op in the compiled HLO.

    Per-op operand shapes are read from the op's result line: for
    all-reduce/all-gather the operands appear as args; we conservatively
    take the op's own result tuple shapes (equal to operand bytes for
    all-reduce; >= operand bytes for all-gather, documented in
    EXPERIMENTS.md). Ops inside while loops are counted once per
    iteration estimate when trip counts are annotated; raw counts are
    also reported.
    """
    totals: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        # Only count op definitions (lhs "x = type[...] op-name(...)")
        op = m.group(1)
        if f" {op}(" not in line and f" {op}-start(" not in line and not re.search(
            rf"= [^=]*{op}", line
        ):
            continue
        lhs = line.split("=", 1)[1]
        shapes = list(_SHAPE_RE.finditer(lhs.split("(", 1)[0]))
        nbytes = sum(_bytes_of_shape(s) for s in shapes)
        totals[op] = totals.get(op, 0.0) + nbytes
        counts[op] = counts.get(op, 0) + 1
    return {"bytes": totals, "counts": counts, "total_bytes": sum(totals.values())}


def while_trip_counts(hlo_text: str) -> list[int]:
    """Trip counts XLA annotates on while loops (scan over layers etc.)."""
    return [int(x) for x in re.findall(r'trip_count["\s:=]+(\d+)', hlo_text)]


def run_one(arch: str, shape_name: str, *, multi_pod: bool, fl_round: bool,
            causal_skip: bool, mesh_shape=None,
            require_seq_sharded: bool = False,
            require_alltoall: bool = False,
            require_flash: bool = False) -> dict:
    import dataclasses

    import jax
    from repro.configs import get_config, long_context_variant
    from repro.launch.mesh import make_production_mesh, mesh_label
    from repro.launch import steps
    from repro.models.config import INPUT_SHAPES
    from repro.obs.profile import span
    from repro.optim import adamw

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k":
        cfg = long_context_variant(cfg)
    if require_flash:
        cfg = dataclasses.replace(cfg, attn_impl="flash")
    mesh = make_production_mesh(multi_pod=multi_pod, shape=mesh_shape)
    n_chips = mesh.devices.size

    with span("dryrun.lower", arch=arch, shape=shape_name) as t_lower:
        if fl_round:
            if mesh.shape.get("pod", 1) < 2:
                raise ValueError(
                    "--fl-round needs a pod axis >= 2 (clients = pods)")
            lowered = steps.lower_fl_round(cfg, mesh, shape)
            step_kind = "fl_round"
        elif shape.kind == "train":
            lowered = steps.lower_train_step(
                cfg, mesh, shape, adamw(3e-4), causal_skip=causal_skip
            )
            step_kind = "train"
        elif shape.kind == "prefill":
            lowered = steps.lower_prefill_step(cfg, mesh, shape)
            step_kind = "prefill"
        else:
            lowered = steps.lower_decode_step(cfg, mesh, shape)
            step_kind = "decode"
    with span("dryrun.compile", arch=arch, shape=shape_name) as t_compile:
        compiled = lowered.compile()

    from repro.dist.hlo_analysis import (
        full_length_intermediates, loop_summary, weighted_collectives,
    )
    from repro.launch.analytic import analytic_record

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = weighted_collectives(hlo)        # loop-aware (primary)
    loops = loop_summary(hlo)

    gates: dict = {}
    if require_seq_sharded:
        # Per-device shapes in compiled SPMD HLO are post-partition: any
        # big tensor still carrying the FULL sequence length was
        # replicated along seq. Threshold 2*B_local*S*d_model bytes keeps
        # the inherent attention k/v window gathers (GQA: KV*hd << D) and
        # token ids below the bar while catching every re-replicated
        # layer-boundary / FFN / MoE activation.
        dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
        b_loc = max(shape.global_batch // dp, 1)
        min_bytes = 2 * b_loc * shape.seq_len * cfg.d_model
        offenders = full_length_intermediates(
            hlo, shape.seq_len, min_bytes=min_bytes
        )
        gates["seq_sharded_ok"] = not offenders
        gates["full_seq_intermediates"] = offenders[:10]
        if offenders:
            raise AssertionError(
                f"{len(offenders)} full-seq intermediates >= {min_bytes}B on a "
                f"seq={mesh.shape.get('seq', 1)} mesh; top: {offenders[:3]}"
            )
    if require_alltoall:
        n_a2a = coll["counts"].get("all-to-all", 0)
        gates["alltoall_count"] = n_a2a
        if not n_a2a:
            raise AssertionError(
                "no all-to-all in compiled HLO (expected expert-sharded "
                f"MoE dispatch on mesh {dict(mesh.shape)})"
            )
    if require_flash:
        from repro.dist.hlo_analysis import no_s2_scores

        # The flash lowering must never materialize attention scores: no
        # per-device tensor may carry O(S^2) elements (S measured per
        # device when the mesh shards seq). On a seq>1 mesh the ring
        # variant must also be the active path — its K/V rotation is the
        # only collective-permute source in these steps.
        seq_sh = mesh.shape.get("seq", 1)
        offenders = no_s2_scores(hlo, shape.seq_len, shards=seq_sh)
        gates["no_s2_scores_ok"] = not offenders
        gates["s2_offenders"] = offenders[:10]
        n_cp = coll["counts"].get("collective-permute", 0)
        gates["ring_collective_permutes"] = n_cp
        if offenders:
            raise AssertionError(
                f"{len(offenders)} O(S^2) score tensors in flash-lowered "
                f"{shape_name} (seq shards={seq_sh}); top: {offenders[:3]}"
            )
        if seq_sh > 1 and not n_cp:
            raise AssertionError(
                "no collective-permute in flash lowering on a "
                f"seq={seq_sh} mesh — ring attention path not taken"
            )

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    dp_size = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    ana = analytic_record(
        cfg, shape, "train" if step_kind in ("train", "fl_round") else step_kind,
        n_chips, causal_skip=causal_skip, dp_size=dp_size,
    )

    # roofline terms: analytic compute/memory (XLA counts loop bodies once),
    # loop-aware HLO parse for collectives.
    compute_s = ana["analytic_flops_per_device"] / PEAK_FLOPS
    memory_s = ana["analytic_bytes_per_device"] / HBM_BW
    collective_s = coll["total_bytes"] / ICI_BW

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_label(mesh),
        "step": step_kind,
        "n_chips": int(n_chips),
        "ok": True,
        **gates,
        "lower_s": round(t_lower.seconds, 2),
        "compile_s": round(t_compile.seconds, 2),
        "hlo_flops_per_device_raw": flops,
        "hlo_bytes_per_device_raw": bytes_acc,
        **ana,
        "collective_bytes_per_device": coll["total_bytes"],
        "collective_breakdown": coll["bytes"],
        "collective_counts": coll["counts"],
        "collective_bytes_raw_unweighted": coll["unweighted_total_bytes"],
        "loops": loops[:40],
        "compute_term_s": compute_s,
        "memory_term_s": memory_s,
        "collective_term_s": collective_s,
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "causal_skip": causal_skip,
    }
    return record


def run_wire_ratio(arch: str, shape_name: str, downlink: str = "off") -> dict:
    """Pod-scale wire accounting (ROADMAP pod-scale item, second half):
    lower the federated round on the 2x16x16 mesh in both wire modes and
    record the per-arch inter-pod byte ratio (uint8 wire / fp32 payload)
    via the replica-group pod-crossing attribution.

    ``downlink`` threads the broadcast mode into BOTH lowered rounds, so
    the ratio measures the full round-trip wire discipline. Because the
    aggregate is already pod-replicated after the uplink gather, the
    broadcast leg adds no inter-pod HLO bytes — the downlink payload is
    over-the-air, accounted analytically in the ``downlink_*`` fields
    (fp32 = 4Z bytes vs wire = Z*q/8 + Z/8 + 4 bytes per client).
    """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.dist.hlo_analysis import (
        inter_axis_bytes, pod_partition_map, wire_payload_split,
    )
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh, mesh_label
    from repro.models import abstract_params
    from repro.models.config import INPUT_SHAPES
    from repro.obs.profile import span

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=True)
    pods = pod_partition_map(mesh)

    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_label(mesh),
        "step": "fl_round_wire_ratio", "downlink": downlink, "ok": True,
    }
    for packed in (False, True):
        with span("dryrun.wire_ratio", packed=packed) as t:
            hlo = steps.lower_fl_round(
                cfg, mesh, shape, wire_packed=packed, downlink=downlink
            ).compile().as_text()
            r = inter_axis_bytes(hlo, pods)
            split = wire_payload_split(r)
            mode = "packed" if packed else "fp32"
            rec[f"{mode}_inter_bytes"] = r["inter_bytes"]
            rec[f"{mode}_unattributed_bytes"] = r["unattributed_bytes"]
            rec[f"{mode}_inter_by_kind"] = r["inter_by_kind"]
            rec[f"{mode}_inter_wire_bytes"] = split["wire_bytes"]
            rec[f"{mode}_inter_dense_bytes"] = split["dense_bytes"]
            rec[f"{mode}_wall_s"] = round(t.seconds, 1)
    # attribution must not silently degrade into the unattributed bucket
    assert rec["fp32_inter_bytes"] > 0 and rec["packed_inter_bytes"] > 0, rec
    assert max(
        rec["fp32_unattributed_bytes"], rec["packed_unattributed_bytes"]
    ) < 0.1 * rec["fp32_inter_bytes"], rec
    rec["inter_pod_ratio"] = rec["packed_inter_bytes"] / rec["fp32_inter_bytes"]
    # over-the-air downlink payloads, per client (eq.-5 accounting at the
    # fixed broadcast level)
    z = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(abstract_params(cfg))
    )
    rec["model_dim_z"] = z
    rec["downlink_fp32_bytes"] = 4 * z
    if downlink != "off":
        q = steps.DOWNLINK_Q_BITS
        rec["downlink_wire_bytes"] = (z * q) // 8 + (z + 7) // 8 + 4
        rec["downlink_ratio"] = rec["downlink_wire_bytes"] / (4.0 * z)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="explicit 2D/3D/4D mesh, e.g. 1x4x2x16 "
                         "(pod x data x seq x model)")
    ap.add_argument("--fl-round", action="store_true")
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--require-seq-sharded", action="store_true")
    ap.add_argument("--require-alltoall", action="store_true")
    ap.add_argument("--require-flash", action="store_true",
                    help="lower with cfg.attn_impl='flash' and fail if the "
                         "compiled HLO carries any per-device O(S^2) score "
                         "tensor (hlo_analysis.no_s2_scores); on a seq>1 "
                         "mesh additionally require the ring variant's "
                         "collective-permute K/V rotation")
    ap.add_argument("--wire-ratio", action="store_true",
                    help="per-arch fl-round inter-pod byte-ratio record "
                         "(both wire modes, 2x16x16 mesh)")
    ap.add_argument("--downlink", default="off",
                    choices=("off", "quant", "delta"),
                    help="server->client broadcast mode threaded into the "
                         "lowered federated round (--wire-ratio only)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    try:
        if args.wire_ratio:
            rec = run_wire_ratio(args.arch, args.shape,
                                 downlink=args.downlink)
        else:
            rec = run_one(
                args.arch, args.shape, multi_pod=args.multi_pod,
                fl_round=args.fl_round, causal_skip=args.causal_skip,
                mesh_shape=args.mesh_shape,
                require_seq_sharded=args.require_seq_sharded,
                require_alltoall=args.require_alltoall,
                require_flash=args.require_flash,
            )
    except Exception as e:  # noqa: BLE001 — the sweep wants the record
        mesh_lbl = args.mesh_shape or (
            "2x16x16" if (args.multi_pod or args.wire_ratio) else "16x16"
        )
        rec = {
            "arch": args.arch, "shape": args.shape, "mesh": mesh_lbl,
            "ok": False, "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    print(json.dumps(rec, indent=2, default=str))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(rec, default=str) + "\n")
    # mirror the record into the run ledger (REPRO_LEDGER) so sweeps
    # that thread a ledger through their subprocesses see per-combo rows
    from repro.obs import default_ledger

    led = default_ledger()
    led.record(f"launch.dryrun[{args.arch},{args.shape}]", rec)
    if rec.get("ok") and "collective_breakdown" in rec:
        led.hlo_event(
            f"launch.dryrun[{args.arch},{args.shape},{rec.get('mesh')}]",
            {
                "collective_bytes_per_device":
                    rec.get("collective_bytes_per_device"),
                "collective_breakdown": rec.get("collective_breakdown"),
                "collective_counts": rec.get("collective_counts"),
            },
        )
    return 0 if rec.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
