"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell, derive three per-step time bounds from the
compiled program (TPU v5e constants, per chip — all terms are per-device
because cost_analysis reports the per-device SPMD program):

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / ICI_bw

plus MODEL_FLOPS (the textbook 6*N*D / 2*N*D useful work) and the
usefulness ratio MODEL_FLOPS / HLO_FLOPs_total, which exposes remat
recompute and dispatch/padding waste.  The "roofline fraction" we report
as the headline score is

  fraction = ideal_compute_time / max(compute, memory, collective)

where ideal_compute_time = MODEL_FLOPS / (chips * peak): the share of the
binding-bound step time spent on useful model math.

Usage:
  PYTHONPATH=src python -m repro.launch.roofline [--results dryrun_results.json]
      [--tag baseline] [--format md|csv]
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import SHAPES, ARCH_IDS, get_config
from repro.launch.mesh import device_peaks

TARGET_KIND = "TPU v5 lite"  # the dry-run planner's target chip


def param_counts(arch: str) -> tuple[float, float]:
    """(total, active) parameter counts from the abstract param tree."""
    from repro.models.model import abstract_params

    cfg = get_config(arch)
    tree = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    total = 0
    routed = 0
    for path, leaf in flat:
        n = int(np.prod(leaf.shape))
        total += n
        names = [str(getattr(p, "key", "")) for p in path]
        if "moe" in names and "shared" not in names and any(
            nm in ("wg", "wu", "wo") for nm in names
        ):
            routed += n
    if cfg.n_experts and routed:
        active = total - routed + routed * cfg.top_k / cfg.n_experts
    else:
        active = total
    return float(total), float(active)


def model_flops(arch: str, shape_name: str) -> float:
    """Textbook useful FLOPs per step (whole job, all chips)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    _, n_active = param_counts(arch)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence (KV-cache attention reads are the
    # memory term's job, not FLOPs)
    return 2.0 * n_active * shape.global_batch


def analyze_cell(key: str, cell: dict) -> dict | None:
    if cell.get("status") != "ok":
        return None
    tag, arch, shape_name, mesh = key.split("/")
    n_dev = cell["n_devices"]
    src = cell.get("analytic") or cell["cost"]  # analytic = trip-corrected
    flops_dev = src["flops_per_device"]
    bytes_dev = src["bytes_per_device"]
    wire = src.get("wire_bytes", cell["collectives"]["wire_bytes"])

    peaks = device_peaks(TARGET_KIND)
    t_compute = flops_dev / peaks["flops_bf16"]
    t_memory = bytes_dev / peaks["hbm_bw"]
    t_coll = wire / peaks["ici_bw"]
    bound = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]

    mf = model_flops(arch, shape_name)
    hlo_total = flops_dev * n_dev
    useful = mf / max(hlo_total, 1.0)
    ideal = mf / (n_dev * peaks["flops_bf16"])
    frac = ideal / max(t_compute, t_memory, t_coll, 1e-30)

    return {
        "key": key, "tag": tag, "arch": arch, "shape": shape_name, "mesh": mesh,
        "t_compute_s": t_compute, "t_memory_s": t_memory, "t_collective_s": t_coll,
        "bound": bound, "model_flops": mf, "hlo_flops_total": hlo_total,
        "useful_ratio": useful, "roofline_fraction": frac,
        "temp_gib": cell["memory"]["temp_bytes"] / 2**30,
        "arg_gib": cell["memory"]["argument_bytes"] / 2**30,
        "compile_s": cell.get("compile_s"),
    }


def load(results_path: str, tag: str = "baseline"):
    with open(results_path) as f:
        results = json.load(f)
    rows, skips = [], []
    for key, cell in sorted(results.items()):
        if not key.startswith(tag + "/"):
            continue
        if cell.get("status") == "skipped":
            skips.append((key, cell["reason"]))
            continue
        r = analyze_cell(key, cell)
        if r:
            rows.append(r)
    return rows, skips


def fmt_md(rows, skips) -> str:
    out = [
        "| arch | shape | mesh | compute s | memory s | collective s | bound "
        "| useful (6ND/HLO) | roofline frac | temp GiB/dev |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
            f"| {r['t_collective_s']:.3e} | **{r['bound']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.3f} "
            f"| {r['temp_gib']:.2f} |"
        )
    if skips:
        out.append("")
        out.append("Skipped cells:")
        for key, why in skips:
            out.append(f"- `{key}`: {why}")
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="dryrun_results.json")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--format", choices=["md", "csv"], default="md")
    args = ap.parse_args()
    rows, skips = load(args.results, args.tag)
    if args.format == "md":
        print(fmt_md(rows, skips))
    else:
        cols = ["arch", "shape", "mesh", "t_compute_s", "t_memory_s",
                "t_collective_s", "bound", "useful_ratio", "roofline_fraction"]
        print(",".join(cols))
        for r in rows:
            print(",".join(str(r[c]) for c in cols))


if __name__ == "__main__":
    main()
