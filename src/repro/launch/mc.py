"""CLI for the chip-ensemble Monte Carlo engine (`repro.mc`).

Two network levels:

  --network layer (default): a population of sampled chip instances of ONE
  IRC layer, Table-II-style mean±std bit-agreement columns (the mAP-drop
  proxy), plus quantiles and throughput.

  --network detector: WHOLE-network MC — a chip population of the IRC
  detector (`DetectorEnsemble`), metric = mAP@0.5 per chip on a synthetic
  IVS-geometry eval batch, i.e. Table II in the paper's own units.  Weights
  are random-init unless `--det-steps` runs a short QAT first, so absolute
  mAP is only meaningful with training; drops and spreads are reported the
  same way either way.

Every run gets an `experiments/<run_id>/` directory (root set by
`--run-dir`; empty string disables) holding `manifest.json` (args, git SHA,
jax versions, host, backend), the `metrics.jsonl` event stream (per-chunk
per-chip values + convergence stderr), per-chip metric vectors as `.npy`,
the machine-readable `results.csv` (or wherever `--out` points), and — with
`--trace` — a `jax.profiler` trace.  stdout carries the human-readable
summary only.

  # 64-chip ensemble, all nonideal effects, proposed design
  PYTHONPATH=src python -m repro.launch.mc --chips 64

  # full Table II ablation sweep, baseline binary mapping, kernel backend
  PYTHONPATH=src python -m repro.launch.mc --chips 128 --scheme binary \
      --bias-rows 0 --ablation table2 --backend kernel

  # per-die bias calibration + JSON report + machine CSV
  PYTHONPATH=src python -m repro.launch.mc --chips 64 --calibrate \
      --json experiments/mc_proposed.json --out mc_proposed.csv

  # adaptive population size: stop when the mean is known to ±0.002
  PYTHONPATH=src python -m repro.launch.mc --chips 1024 \
      --stderr-target 0.002

  # whole-detector population mAP, smoke geometry, 16 chips, with trace
  PYTHONPATH=src python -m repro.launch.mc --network detector --chips 16 \
      --det-steps 100 --ablation table2 --trace

  # detector sweep with the Pallas chip-batched kernel forced onto every
  # group matmul (auto consults src/repro/kernels/tuning.json instead)
  PYTHONPATH=src python -m repro.launch.mc --network detector --chips 4 \
      --chunk 2 --det-backend kernel

  # ensemble-aware QAT: single-draw vs 4-chip-population training, scored
  # side by side with whole-network population mAP
  PYTHONPATH=src python -m repro.launch.mc --network detector --chips 16 \
      --det-steps 100 --train-chips 4

  # aging timeline: measured device backend swept over deployment ages —
  # every ablation column repeats per age ("mAP after N days" curves)
  PYTHONPATH=src python -m repro.launch.mc --network detector --chips 16 \
      --device-model measured --t-days 0,30,365
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache


def build_layer(args):
    import jax
    import jax.numpy as jnp
    from repro.core import (ternary_quantize, binary_quantize, ternary_planes,
                            binary_planes, ideal_ternary_matmul)

    k_w, k_x = jax.random.split(jax.random.PRNGKey(args.seed))
    w_lat = jax.random.normal(k_w, (args.fan_in, args.n_out))
    if args.scheme == "ternary":
        w = ternary_quantize(w_lat)
        mapped = ternary_planes(w, bias_rows=args.bias_rows)
    else:
        w = binary_quantize(w_lat)
        mapped = binary_planes(w)
    x = (jax.random.uniform(k_x, (args.batch, args.fan_in))
         > 1.0 - args.density).astype(jnp.float32)
    ref_bits = (ideal_ternary_matmul(x, w) > 0).astype(jnp.float32)
    return mapped, x, ref_bits


def _parse_t_days(text):
    """--t-days "0,30,365" -> [0.0, 30.0, 365.0] (one age per sweep pass)."""
    try:
        ts = [float(t) for t in str(text).split(",") if t.strip() != ""]
    except ValueError:
        raise SystemExit(f"--t-days must be a comma list of numbers, "
                         f"got {text!r}")
    if not ts:
        raise SystemExit("--t-days needs at least one age")
    if any(t < 0 for t in ts):
        raise SystemExit("--t-days ages must be >= 0")
    return ts


def _age_label(name, t, ts):
    """Column label with the age suffixed when sweeping multiple ages."""
    return name if len(ts) == 1 else f"{name}@t{t:g}d"


def _ablation_columns(args, table):
    """Resolve --ablation into named columns; the ideal column always runs
    (drop_vs_ideal is measured against the simulated ideal, never 1.0)."""
    if args.ablation == "table2":
        return list(table)
    by_name = dict(table)
    if args.ablation not in by_name:
        raise SystemExit(f"unknown ablation column: {args.ablation!r} "
                         f"(choices: table2, {', '.join(by_name)})")
    columns = [("ideal", by_name["ideal"])]
    if args.ablation != "ideal":
        columns.append((args.ablation, by_name[args.ablation]))
    return columns


def _make_runlog(args):
    """RunLog under `<run-dir>/<run_id>/` (NullRunLog when --run-dir '')."""
    from repro.obs import maybe_runlog
    obs = maybe_runlog(bool(args.run_dir), f"mc-{args.network}",
                       args=vars(args), root=args.run_dir,
                       run_id=args.run_id or None)
    if obs.path is not None:
        print(f"# run dir: {obs.path}")
    if args.trace:
        obs.start_trace()
    return obs


def _write_csv(args, obs, lines) -> None:
    """Machine-readable CSV through the obs writer: `--out PATH` wins, else
    `<run_dir>/results.csv`; stdout stays human-readable either way."""
    text = "\n".join(lines) + "\n"
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(text)
    else:
        out = obs.write_text("results.csv", text)
    if out is not None:
        print(f"# wrote {out}")


def _write_report(args, obs, report) -> None:
    obs.write_text("report.json", json.dumps(report, indent=1))
    if not args.json:
        return
    out = Path(args.json)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    print(f"# wrote {out}")


def _train_checkpoints(args, det, data):
    """QAT checkpoint(s) to sweep: the legacy single path, or — with
    --train-chips N — a single-draw vs N-chip-ensemble QAT pair trained from
    the SAME root key with the surrogate-noise config on, so the population
    sweep isolates what the chips axis buys (paper Sec. V)."""
    import jax
    from repro.core import NonidealConfig
    if args.train_chips <= 1:
        if args.det_steps:
            from repro.train.det_qat import quick_qat
            return {"qat": quick_qat(det, data, args.det_steps,
                                     args.det_batch, seed=args.seed)}
        return {"init": det.init(jax.random.PRNGKey(args.seed))}
    if not args.det_steps:
        raise SystemExit("--train-chips needs --det-steps > 0 "
                         "(it compares QAT'd checkpoints)")
    from repro.train.det_qat import quick_qat
    noise = NonidealConfig.all()   # surrogate models devvar + SA of this set
    root = jax.random.PRNGKey(args.seed + 1)
    common = dict(seed=args.seed, key=root, cfg_ni=noise)
    return {
        "single": quick_qat(det, data, args.det_steps, args.det_batch,
                            train_chips=1, **common),
        f"ens{args.train_chips}": quick_qat(
            det, data, args.det_steps, args.det_batch,
            train_chips=args.train_chips,
            resample_every=args.resample_every, **common),
    }


def run_detector(args) -> None:
    """Whole-network MC: population mAP@0.5 of the smoke-geometry detector."""
    import jax
    from repro.configs import yolo_irc
    from repro.data.detection import SyntheticDetectionData
    from repro.device import get_device_model
    from repro.models import IRCDetector
    from repro.mc import McConfig, run_mc_detector, TABLE2_ABLATION
    from repro.obs import PhaseTimer

    obs = _make_runlog(args)
    cfg = yolo_irc.smoke(args.det_scheme)
    det = IRCDetector(cfg)
    data = SyntheticDetectionData(img_hw=cfg.img_hw, stride=cfg.strides,
                                  n_classes=cfg.n_classes,
                                  n_anchors=cfg.n_anchors)
    qat_timer = PhaseTimer("qat", unit="checkpoints")
    with qat_timer.lap() as lap:
        checkpoints = _train_checkpoints(args, det, data)
        lap.items = len(checkpoints)
    qat_timer.log_to(obs, det_steps=args.det_steps,
                     train_chips=args.train_chips)
    # deployment calibration: stem running stats (+ baseline block BN)
    calib = data.batch_for_step(999, args.det_batch * 4)
    ev = data.batch_for_step(1000, args.det_batch)

    mc = McConfig(n_chips=args.chips, chunk_size=args.chunk)
    key = jax.random.PRNGKey(args.seed)
    columns = _ablation_columns(args, TABLE2_ABLATION)
    ts = _parse_t_days(args.t_days)
    # auto defers to the committed kernels/tuning.json; kernel forces the
    # Pallas chip-batched path (interpret mode on CPU)
    use_kernel = {"auto": None, "jnp": False, "kernel": True}[args.det_backend]

    print(f"# detector {args.det_scheme} {cfg.img_hw[0]}x{cfg.img_hw[1]} "
          f"batch={args.det_batch} chips={args.chips} "
          f"qat_steps={args.det_steps} train_chips={args.train_chips} "
          f"backend={args.det_backend} "
          f"pipeline={not args.no_pipeline} "
          f"device={args.device_model} t_days={','.join(f'{t:g}' for t in ts)}")
    print(f"{'checkpoint':10s} {'config':14s} {'map50 mean±std':>16s} "
          f"{'drop':>7s} {'q05':>7s} {'q50':>7s} {'q95':>7s} "
          f"{'chips':>5s} {'chips/s':>8s} {'compile_s':>9s}")
    csv_lines = ["checkpoint,config,map50_mean,map50_std,drop_vs_ideal,"
                 "q05,q50,q95,chips,chips_per_s,compile_s,"
                 "device_model,t_days"]
    report = {"args": vars(args), "run_id": obs.manifest.get("run_id"),
              "results": {}}
    for ck, params in checkpoints.items():
        params = det.calibrate_bn(params, calib.images)
        report["results"][ck] = {}
        for t in ts:
            device = get_device_model(args.device_model, t_days=t)
            results = {}
            for name, cfg_ni in columns:
                obs.log_event("ablation_column", checkpoint=ck, column=name,
                              device_model=args.device_model, t_days=t)
                results[name] = run_mc_detector(
                    key, det, params, ev.images, ev.boxes, ev.classes,
                    mc=dataclasses.replace(mc, cfg=cfg_ni, device=device),
                    obs=obs, stderr_target=args.stderr_target,
                    pipeline=not args.no_pipeline, use_kernel=use_kernel)
            # the drop is measured against the SAME age's simulated ideal
            ideal_mean = results["ideal"].metrics["map50"]["mean"]
            for name, res in results.items():
                label = _age_label(name, t, ts)
                m = res.metrics["map50"]
                drop = ideal_mean - m["mean"]
                print(f"{ck:10s} {label:14s} "
                      f"{m['mean']:8.4f}±{m['std']:6.4f} {drop:7.4f} "
                      f"{m.get('q05', float('nan')):7.4f} "
                      f"{m.get('q50', float('nan')):7.4f} "
                      f"{m.get('q95', float('nan')):7.4f} "
                      f"{res.n_chips:5d} {res.chips_per_sec:8.2f} "
                      f"{res.compile_s:9.2f}")
                csv_lines.append(
                    f"{ck},{label},{m['mean']:.6f},{m['std']:.6f},"
                    f"{drop:.6f},"
                    f"{m.get('q05', float('nan')):.6f},"
                    f"{m.get('q50', float('nan')):.6f},"
                    f"{m.get('q95', float('nan')):.6f},{res.n_chips},"
                    f"{res.chips_per_sec:.2f},{res.compile_s:.4f},"
                    f"{args.device_model},{t:g}")
                obs.save_array(f"per_chip_map50_{ck}_{label}",
                               res.per_chip["map50"])
                report["results"][ck][label] = {
                    "metrics": res.metrics, "wall_s": res.wall_s,
                    "compile_s": res.compile_s,
                    "chips_per_sec": res.chips_per_sec,
                    "device_s": res.device_s, "host_s": res.host_s,
                    "device_model": args.device_model, "t_days": t,
                    "per_chip_map50": res.per_chip["map50"].tolist()}
    _write_csv(args, obs, csv_lines)
    _write_report(args, obs, report)
    obs.finalize(status="ok", network="detector",
                 device_model=args.device_model, t_days=ts)


def run_layer(args) -> None:
    import jax
    from repro.device import get_device_model
    from repro.mc import McConfig, run_mc, TABLE2_ABLATION

    obs = _make_runlog(args)
    mapped, x, ref_bits = build_layer(args)
    mc = McConfig(n_chips=args.chips, chunk_size=args.chunk,
                  accumulation=args.accumulation, backend=args.backend,
                  calibrate=args.calibrate)
    key = jax.random.PRNGKey(args.seed)
    ts = _parse_t_days(args.t_days)
    columns = _ablation_columns(args, TABLE2_ABLATION)

    print(f"# {args.scheme} {args.fan_in}x{args.n_out} batch={args.batch} "
          f"chips={args.chips} backend={args.backend} "
          f"device={args.device_model} t_days={','.join(f'{t:g}' for t in ts)}"
          + (" calibrated" if args.calibrate else ""))
    print(f"{'config':14s} {'agree mean±std':>16s} {'drop':>7s} "
          f"{'q05':>7s} {'q50':>7s} {'q95':>7s} {'chips':>5s} "
          f"{'chips/s':>8s} {'compile_s':>9s}")
    csv_lines = ["config,agree_mean,agree_std,drop_vs_ideal,q05,q50,q95,"
                 "chips,chips_per_s,compile_s,device_model,t_days"]
    report = {"args": vars(args), "run_id": obs.manifest.get("run_id"),
              "results": {}}
    for t in ts:
        device = get_device_model(args.device_model, t_days=t)
        results = {}
        for name, cfg in columns:
            obs.log_event("ablation_column", column=name,
                          device_model=args.device_model, t_days=t)
            results[name] = run_mc(
                key, mapped, x, ref_bits=ref_bits,
                mc=dataclasses.replace(mc, cfg=cfg, device=device), obs=obs,
                stderr_target=args.stderr_target)
        # the drop is measured against the SAME age's simulated ideal
        ideal_mean = results["ideal"].metrics["bit_agreement"]["mean"]
        for name, res in results.items():
            label = _age_label(name, t, ts)
            m = res.metrics["bit_agreement"]
            drop = ideal_mean - m["mean"]
            print(f"{label:14s} {m['mean']:8.4f}±{m['std']:6.4f} {drop:7.4f} "
                  f"{m.get('q05', float('nan')):7.4f} "
                  f"{m.get('q50', float('nan')):7.4f} "
                  f"{m.get('q95', float('nan')):7.4f} "
                  f"{res.n_chips:5d} {res.chips_per_sec:8.2f} "
                  f"{res.compile_s:9.2f}")
            csv_lines.append(
                f"{label},{m['mean']:.6f},{m['std']:.6f},{drop:.6f},"
                f"{m.get('q05', float('nan')):.6f},"
                f"{m.get('q50', float('nan')):.6f},"
                f"{m.get('q95', float('nan')):.6f},{res.n_chips},"
                f"{res.chips_per_sec:.2f},{res.compile_s:.4f},"
                f"{args.device_model},{t:g}")
            for metric in ("bit_agreement", "ones_fraction"):
                obs.save_array(f"per_chip_{metric}_{label}",
                               res.per_chip[metric])
            report["results"][label] = {
                "metrics": res.metrics, "wall_s": res.wall_s,
                "compile_s": res.compile_s,
                "chips_per_sec": res.chips_per_sec,
                "device_model": args.device_model, "t_days": t,
                "per_chip_bit_agreement":
                    res.per_chip["bit_agreement"].tolist(),
                "bias_units": (res.bias_units.tolist()
                               if res.bias_units is not None else None)}
    _write_csv(args, obs, csv_lines)
    _write_report(args, obs, report)
    obs.finalize(status="ok", network="layer",
                 device_model=args.device_model, t_days=ts)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="chip-ensemble Monte Carlo sweep (repro.mc)")
    ap.add_argument("--network", default="layer",
                    choices=["layer", "detector"],
                    help="layer: one IRC layer, bit-agreement proxy; "
                         "detector: whole-network mAP@0.5 population sweep")
    ap.add_argument("--det-scheme", default="ternary",
                    choices=["ternary", "binary"],
                    help="detector design (proposed ternary | baseline binary)")
    ap.add_argument("--det-batch", type=int, default=2,
                    help="detector eval batch size")
    ap.add_argument("--det-steps", type=int, default=0,
                    help="short QAT before the detector sweep (0 = random init)")
    ap.add_argument("--train-chips", type=int, default=1,
                    help="ensemble-aware QAT: train a second checkpoint "
                         "against N-chip populations (surrogate noise on) and "
                         "report population mAP for single-draw vs ensemble "
                         "QAT side by side (needs --det-steps)")
    ap.add_argument("--resample-every", type=int, default=1,
                    help="QAT steps between chip-population resamples")
    ap.add_argument("--det-backend", default="auto",
                    choices=["auto", "jnp", "kernel"],
                    help="detector crossbar matmul routing: auto consults "
                         "the committed kernels/tuning.json, jnp forces the "
                         "reference ensemble path, kernel forces the Pallas "
                         "chip-batched kernel (interpret mode on CPU)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="serial chunk loop (eager ensemble build + blocking "
                         "forward) instead of the double-buffered pipeline")
    ap.add_argument("--chips", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--fan-in", type=int, default=540)
    ap.add_argument("--n-out", type=int, default=60)
    ap.add_argument("--density", type=float, default=0.5,
                    help="activated word-line fraction")
    ap.add_argument("--scheme", default="ternary",
                    choices=["ternary", "binary"])
    ap.add_argument("--bias-rows", type=int, default=32)
    ap.add_argument("--accumulation", default="single_shot",
                    choices=["single_shot", "partial_sum"])
    ap.add_argument("--backend", default="jnp", choices=["jnp", "kernel"])
    ap.add_argument("--ablation", default="all",
                    help="'table2' for the full effect sweep, or one column "
                         "name (ideal|devvar|devvar+nl|devvar+nl+peri|all)")
    ap.add_argument("--device-model", default="analytic",
                    choices=["analytic", "measured"],
                    help="repro.device backend chips are sampled from: "
                         "analytic (the paper's closed forms, default) or "
                         "measured (the packaged tabulated dataset)")
    ap.add_argument("--t-days", default="0",
                    help="comma list of deployment ages in days; each age "
                         "wraps the backend in a RetentionDrift timeline and "
                         "repeats the sweep (0 = programming day; e.g. "
                         "'0,30,365' for an aging curve)")
    ap.add_argument("--calibrate", action="store_true",
                    help="per-die extra-bias calibration before evaluation")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="", help="write the report here")
    ap.add_argument("--run-dir", default="experiments",
                    help="root for the experiments/<run_id>/ run directory "
                         "(manifest + metrics.jsonl + per-chip .npy; "
                         "'' disables)")
    ap.add_argument("--run-id", default="",
                    help="explicit run id (default: timestamped)")
    ap.add_argument("--out", default="",
                    help="machine-readable CSV path "
                         "(default <run_dir>/results.csv)")
    ap.add_argument("--stderr-target", type=float, default=None,
                    help="stop each sweep once the standard error of the "
                         "mean reaches this target (adaptive chip count)")
    ap.add_argument("--trace", action="store_true",
                    help="capture a jax.profiler trace into the run dir")
    args = ap.parse_args()
    enable_compile_cache()

    if args.network == "detector":
        # layer-only knobs have no detector equivalent: fail loudly rather
        # than emit a report whose vars(args) provenance silently lies
        layer_only = ("scheme", "fan_in", "n_out", "density", "bias_rows",
                      "accumulation", "backend", "calibrate", "batch")
        misused = [f"--{n.replace('_', '-')}" for n in layer_only
                   if getattr(args, n) != ap.get_default(n)]
        if misused:
            raise SystemExit(
                f"--network detector does not take {', '.join(misused)} "
                f"(layer-path flags; use --det-scheme/--det-batch/"
                f"--det-steps)")
        run_detector(args)
        return

    det_only = ("train_chips", "resample_every", "det_backend", "no_pipeline")
    misused = [f"--{n.replace('_', '-')}" for n in det_only
               if getattr(args, n) != ap.get_default(n)]
    if misused:
        raise SystemExit(f"--network layer does not take {', '.join(misused)} "
                         f"(detector QAT flags)")
    run_layer(args)


if __name__ == "__main__":
    main()
