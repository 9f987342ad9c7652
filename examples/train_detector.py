"""End-to-end driver (the paper's task): train the IRC object detector with
QAT on synthetic IVS-geometry data, then evaluate the full structural
crossbar simulation under the paper's nonideal-effect ablation (Table II)
for BOTH designs:

  proposed : ternary 20/60/20, no BN, single-shot, extra bias
  baseline : binary + shared reference, in-memory BN, partial sums

The ablation runs as chip-population Monte Carlo (`run_ablation_detector`):
each column reports POPULATION mean±std mAP@0.5 over `--mc-chips` sampled
dies, and the per-chip metric vectors, the QAT step timing (compile vs
steady-state), and the per-chunk convergence stream land in an
`experiments/<run_id>/` run directory (manifest.json + metrics.jsonl +
per-chip .npy; `--run-dir ''` disables, `--trace` adds a profiler trace).

Defaults are CPU-sized (32x32 images, ~200 steps, a few minutes); pass
--full for the paper's 1024x576 geometry (cluster-scale).

  PYTHONPATH=src python examples/train_detector.py --steps 200
"""
import argparse

import jax

from repro.configs import yolo_irc
from repro.core import NonidealConfig
from repro.data.detection import SyntheticDetectionData
from repro.launch.compile_cache import enable_compile_cache
from repro.mc import McConfig, run_ablation_detector
from repro.models import IRCDetector
from repro.obs import NULL_RUNLOG, PhaseTimer, maybe_runlog, timed_step
from repro.optim import AdamWConfig, adamw_init, warmup_step_decay
from repro.train.steps import ensemble_key_for_step, make_det_qat_step

ABLATION = [
    ("ideal", NonidealConfig.none()),
    ("dev-var", NonidealConfig(device_variation=True)),
    ("dev+nl", NonidealConfig(device_variation=True, nonlinearity=True)),
    ("dev+nl+sa", NonidealConfig(device_variation=True, nonlinearity=True,
                                 sa_variation=True, sensing_range=True)),
    ("all", NonidealConfig.all()),
]


def train(det, data, steps, batch, lr, seed=0, noise_cfg=NonidealConfig.none(),
          train_chips=1, resample_every=1, key=None, obs=NULL_RUNLOG,
          design=""):
    """QAT on the shared step builder (`repro.train.steps.make_det_qat_step`).

    `train_chips=1` is the legacy single-draw surrogate; >=2 trains against a
    chip population (ensemble-aware QAT, paper Sec. V at population scale).
    `key` roots BOTH the per-step noise stream and the chip-population
    stream, so a run is reproducible from one key (defaults to the
    historical PRNGKey(1)).  Steps are phase-timed: the first call's
    compile latency is split from the steady-state steps/sec, both logged
    through `obs`.
    """
    params = det.init(jax.random.PRNGKey(seed))
    opt = adamw_init(params)
    timer = PhaseTimer("qat_step", unit="steps")
    step_fn = timed_step(jax.jit(make_det_qat_step(
        det, train_chips=train_chips, cfg_ni=noise_cfg,
        opt_cfg=AdamWConfig(weight_decay=1e-3))), timer)  # paper: AdamW 1e-3
    root = jax.random.PRNGKey(1) if key is None else key

    for s in range(steps):
        b = data.batch_for_step(s, batch)
        lr_s = warmup_step_decay(s, base_lr=lr, warmup_steps=max(steps // 10, 1),
                                 decay_points=((int(steps * 0.7), lr / 10),
                                               (int(steps * 0.9), lr / 100)))
        params, opt, loss = step_fn(params, opt, b.images, b.targets, lr_s,
                                    jax.random.fold_in(root, s),
                                    ensemble_key_for_step(root, s,
                                                          resample_every))
        if s % max(steps // 10, 1) == 0:
            print(f"  step {s:4d}  loss {float(loss):8.4f} "
                  f"({timer.total_s:5.1f}s)", flush=True)
            obs.log_event("train_step", design=design, step=s,
                          loss=float(loss), step_time_s=timer.last_s)
    timer.log_to(obs, design=design, train_chips=train_chips)
    print(f"  qat: compile {timer.compile_s:.1f}s, "
          f"{timer.rate():.2f} steps/s steady", flush=True)
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--mc-chips", type=int, default=8,
                    help="chip-population size per ablation column")
    ap.add_argument("--mc-chunk", type=int, default=0,
                    help="MC chunk size (0 = whole population per chunk)")
    ap.add_argument("--stderr-target", type=float, default=None,
                    help="stop each MC column once the mAP standard error "
                         "reaches this target")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale 1024x576 geometry")
    ap.add_argument("--designs", default="proposed,baseline")
    ap.add_argument("--qat-noise", action="store_true",
                    help="variation-aware QAT: surrogate nonideal noise "
                         "during training (paper Sec. V)")
    ap.add_argument("--train-chips", type=int, default=1,
                    help="ensemble-aware QAT: chip realizations per step "
                         "(implies --qat-noise; 1 = legacy single draw)")
    ap.add_argument("--resample-every", type=int, default=1,
                    help="QAT steps between chip-population resamples")
    ap.add_argument("--run-dir", default="experiments",
                    help="root for the experiments/<run_id>/ run directory "
                         "('' disables)")
    ap.add_argument("--run-id", default="")
    ap.add_argument("--trace", action="store_true",
                    help="capture a jax.profiler trace into the run dir")
    args = ap.parse_args()
    enable_compile_cache()

    obs = maybe_runlog(bool(args.run_dir), "train-detector",
                       args=vars(args), root=args.run_dir,
                       run_id=args.run_id or None)
    if obs.path is not None:
        print(f"# run dir: {obs.path}")
    if args.trace:
        obs.start_trace()

    noise_cfg = (NonidealConfig.all()
                 if (args.qat_noise or args.train_chips > 1)
                 else NonidealConfig.none())
    mc = McConfig(n_chips=args.mc_chips,
                  chunk_size=args.mc_chunk or args.mc_chips)
    results = {}
    for design in args.designs.split(","):
        cfg = (yolo_irc.proposed() if design == "proposed"
               else yolo_irc.baseline()) if args.full else \
            yolo_irc.smoke("ternary" if design == "proposed" else "binary")
        det = IRCDetector(cfg)
        data = SyntheticDetectionData(img_hw=cfg.img_hw,
                                      stride=2 ** (len(cfg.stage_channels) + 1),
                                      n_classes=cfg.n_classes,
                                      n_anchors=cfg.n_anchors)
        print(f"\n=== {design} design: QAT ({args.steps} steps, "
              f"train_chips={args.train_chips}) ===")
        params = train(det, data, args.steps, args.batch, args.lr,
                       noise_cfg=noise_cfg, train_chips=args.train_chips,
                       resample_every=args.resample_every, obs=obs,
                       design=design)
        # deployment step (both designs): populate the digital stem's running
        # stats — eval mode normalizes with them — and, for the baseline, the
        # block BN stats the in-memory BN fold maps into bias cells
        calib = data.batch_for_step(999, args.batch * 4)
        params = det.calibrate_bn(params, calib.images)

        print(f"=== {design}: population MC ablation "
              f"({args.mc_chips} chips) ===")
        ev = data.batch_for_step(1000, args.batch * args.eval_batches)
        sweeps = run_ablation_detector(
            jax.random.PRNGKey(7000), det, params, ev.images, ev.boxes,
            ev.classes, ablations=ABLATION, mc=mc, obs=obs,
            stderr_target=args.stderr_target)
        results[design] = {}
        for name, res in sweeps.items():
            m = res.metrics["map50"]
            results[design][name] = (m["mean"] * 100, m["std"] * 100)
            obs.save_array(f"per_chip_map50_{design}_{name}",
                           res.per_chip["map50"])
            print(f"  {name:10s} mAP {m['mean'] * 100:5.1f} "
                  f"± {m['std'] * 100:4.1f}  "
                  f"({res.n_chips} chips, {res.chips_per_sec:.2f} chips/s "
                  f"steady, compile {res.compile_s:.1f}s)")

    print("\n=== Table II (synthetic-data analog, population mean) ===")
    header = "design     " + "".join(f"{n:>12s}" for n, _ in ABLATION)
    print(header)
    for design, r in results.items():
        row = f"{design:10s}" + "".join(f"{r[n][0]:12.1f}" for n, _ in ABLATION)
        print(row)
    summary = {}
    if {"proposed", "baseline"} <= results.keys():
        drop_p = results["proposed"]["ideal"][0] - results["proposed"]["all"][0]
        drop_b = results["baseline"]["ideal"][0] - results["baseline"]["all"][0]
        summary = {"drop_proposed": drop_p, "drop_baseline": drop_b}
        print(f"\nmAP drop under all effects: proposed {drop_p:.1f}, "
              f"baseline {drop_b:.1f} (paper: 3.85 vs catastrophic)")
    obs.finalize(status="ok", **summary)


if __name__ == "__main__":
    main()
