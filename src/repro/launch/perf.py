"""Perf-iteration driver for the §Perf hillclimb.

Lowers ONE (arch x shape) cell under a named variant of tuning knobs, runs
the two cost probes, and prints the reconstructed roofline terms — the
measure step of the hypothesis -> change -> measure loop.  Results append to
experiments/perf/<arch>__<shape>__<variant>.json so EXPERIMENTS.md §Perf can
table them.

  python -m repro.launch.perf --arch llama3-405b --shape decode_32k \
      --variant baseline
  python -m repro.launch.perf --arch qwen3-moe-235b-a22b --shape train_4k \
      --variant mb4 --microbatch 4
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")
# a CPU-only tool: this process and every JAX child it spawns (they inherit
# the environment) stay off the accelerator, which belongs to one process
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import sys
import traceback
from pathlib import Path

PERF_DIR = Path(__file__).resolve().parents[3] / "experiments" / "perf"


def run_variant(arch: str, shape: str, variant: str, knobs: dict) -> dict:
    from repro.launch.dryrun import lower_cell, probe_pair
    from repro.launch.roofline import (PEAK_FLOPS, HBM_BW, ICI_BW,
                                       _metrics, _rwkv_recurrence_flops)
    from repro.configs.registry import get_config

    cfg = get_config(arch, "full")
    l1, l2 = probe_pair(arch)
    probe_knobs = dict(knobs)
    mb_knob = probe_knobs.pop("microbatch", 0)
    recs = {}
    from repro.configs.shapes import SHAPES
    points = [(l1, 1), (l2, 1)]
    if SHAPES[shape].kind == "train":
        points += [(l1, 2), (l2, 2)]
    for pl, pmb in points:
        recs[(pl, pmb)] = lower_cell(arch, shape, "single", "full",
                                     probe_layers=pl, microbatch=pmb,
                                     **probe_knobs)
        assert recs[(pl, pmb)]["status"] == "ok", recs[(pl, pmb)]
    m1, m2 = _metrics(recs[(l1, 1)]), _metrics(recs[(l2, 1)])
    n_scanned = cfg.n_layers - cfg.n_dense_prefix

    def extrapolate(v1, v2):
        body = v2 - v1
        return max((v1 - body) + body * n_scanned, 0.0)

    totals = {k: extrapolate(m1[k], m2[k]) for k in m1}
    kind = recs[(l1, 1)]["kind"]
    mb_prod = mb_knob or (
        max(1, recs[(l1, 1)]["global_batch"] // 32) if kind == "train" else 1)
    if kind == "train":
        m1m, m2m = _metrics(recs[(l1, 2)]), _metrics(recs[(l2, 2)])
        for k in list(totals):
            if not k.startswith("coll"):
                continue
            par1, par2 = m1m[k] - m1[k], m2m[k] - m2[k]
            act1, act2 = m1[k] - par1, m2[k] - par2
            totals[k] = extrapolate(act1, act2) + mb_prod * extrapolate(par1,
                                                                        par2)
    totals["flops"] += _rwkv_recurrence_flops(
        cfg, kind, recs[(l1, 1)]["global_batch"], recs[(l1, 1)]["seq_len"],
        max(recs[(l1, 1)]["devices"] // 16, 1))
    tokens = recs[(l1, 1)]["global_batch"] * (
        recs[(l1, 1)]["seq_len"] if kind != "decode" else 1)
    model_flops_dev = ((6.0 if kind == "train" else 2.0)
                       * cfg.active_param_count() * tokens
                       / recs[(l1, 1)]["devices"])
    terms = {"compute_s": totals["flops"] / PEAK_FLOPS,
             "memory_s": totals["bytes"] / HBM_BW,
             "collective_s": totals["coll"] / ICI_BW}
    dominant = max(terms, key=terms.get)
    out = {
        "arch": arch, "shape": shape, "variant": variant, "knobs": knobs,
        "flops_dev": totals["flops"], "bytes_dev": totals["bytes"],
        "coll_dev": totals["coll"], **terms,
        "dominant": dominant,
        "bound_s": terms[dominant],
        "model_flops_dev": model_flops_dev,
        "useful_ratio": model_flops_dev / max(totals["flops"], 1.0),
        "roofline_fraction": (model_flops_dev / PEAK_FLOPS)
        / max(terms[dominant], 1e-12),
        "coll_breakdown": {k[5:]: v for k, v in totals.items()
                           if k.startswith("coll_")},
        "memory_analysis_probe": recs[(l2, 1)].get("memory_analysis", {}),
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", default="block",
                    choices=["block", "dots", "names", "none"])
    ap.add_argument("--attn-mode", default=None,
                    choices=[None, "kv_heads", "q_groups", "kv_seq"])
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel residual stream (Megatron SP)")
    args = ap.parse_args()

    knobs = {"remat": args.remat}
    if args.microbatch:
        knobs["microbatch"] = args.microbatch
    if args.attn_mode:
        knobs["attn_mode"] = args.attn_mode
    if args.sp:
        knobs["act_overrides"] = {"act_seq": ("model",)}
    PERF_DIR.mkdir(parents=True, exist_ok=True)
    try:
        rec = run_variant(args.arch, args.shape, args.variant, knobs)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "variant": args.variant, "status": "error",
               "traceback": traceback.format_exc()}
    out = PERF_DIR / f"{args.arch}__{args.shape}__{args.variant}.json"
    out.write_text(json.dumps(rec, indent=1))
    show = {k: rec.get(k) for k in ("variant", "compute_s", "memory_s",
                                    "collective_s", "dominant",
                                    "roofline_fraction", "useful_ratio")}
    print(json.dumps(show, indent=1))
    if "traceback" in rec:
        print(rec["traceback"][-1500:], file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
