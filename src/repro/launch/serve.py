"""Serving launcher: batched request serving for both network families.

`--network lm` (default) restores (or inits) a language model and serves
batched prompts with the slot-wave `ServeEngine` — the decode step is the
exact function the dry-run's `decode_*` cells lower for the production
meshes.  `--network detector` builds the IRC detector and serves a batch of
synthetic images through the population-aware `DetectorServeEngine`: every
request is answered by a chip committee with mean/std/quantile confidence
(runbook: docs/serving.md).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
      --requests 8 --slots 4 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --network detector \
      --requests 6 --slots 2 --committee 4 --run-dir experiments
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs.registry import get_config, list_archs
from repro.launch.compile_cache import enable_compile_cache


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="lm", choices=["lm", "detector"])
    # LM engine
    ap.add_argument("--arch", default="phi3-medium-14b", choices=list_archs())
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a training checkpoint")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    # detector engine
    ap.add_argument("--det-scheme", default="ternary",
                    choices=["ternary", "binary"],
                    help="[detector] weight mapping scheme")
    ap.add_argument("--committee", type=int, default=4,
                    help="[detector] chips answering each request")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="[detector] admission-control queue bound")
    ap.add_argument("--det-backend", default="auto",
                    choices=["auto", "jnp", "kernel"],
                    help="[detector] grouped-matmul backend routing")
    # shared
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--run-dir", default="",
                    help="experiments/<run_id>/ run directory root "
                         "(per-wave telemetry; '' disables)")
    ap.add_argument("--run-id", default="")
    ap.add_argument("--trace", action="store_true",
                    help="capture a jax.profiler trace into the run dir")
    return ap


def _check_flag_use(ap: argparse.ArgumentParser,
                    args: argparse.Namespace) -> None:
    """Fail fast on flags that silently do nothing for the chosen network."""
    lm_only = ["arch", "variant", "ckpt_dir", "max_new", "max_len",
               "temperature"]
    det_only = ["det_scheme", "committee", "max_queue", "det_backend"]
    misused = lm_only if args.network == "detector" else det_only
    for n in misused:
        if getattr(args, n) != ap.get_default(n):
            ap.error(f"--{n.replace('_', '-')} only applies to "
                     f"--network {'lm' if n in lm_only else 'detector'}")


def _serve_lm(args, obs) -> None:
    from repro.models import LM
    from repro.serve import ServeEngine

    cfg = get_config(args.arch, args.variant)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        template = jax.eval_shape(lambda: params)
        try:  # params-only checkpoint
            restored, step = mgr.restore_latest(template)
        except KeyError:  # training checkpoint: TrainState paths (params/...)
            restored, step = mgr.restore_latest({"params": template})
            restored = restored["params"] if restored else None
        if restored is not None:
            params = restored
            print(f"restored step {step} from {args.ckpt_dir}")

    engine = ServeEngine(lm, params, batch_slots=args.slots,
                         max_len=args.max_len, seed=args.seed,
                         temperature=args.temperature, obs=obs)
    rng = jax.random.PRNGKey(1)
    prompts = []
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        n = 2 + i % 6
        prompts.append([int(t) for t in
                        jax.random.randint(k, (n,), 0, cfg.vocab_size)])
    t0 = time.time()
    results = engine.generate(prompts, max_new_tokens=args.max_new)
    dt = time.time() - t0
    new = sum(len(r.tokens) for r in results)
    for i, r in enumerate(results[:4]):
        print(f"req {i}: {len(r.prompt)} prompt toks -> {r.tokens[:8]}...")
    decode = engine.stats()["decode"]
    print(f"{len(results)} requests, {new} new tokens, {dt:.1f}s "
          f"({new/dt:.1f} tok/s overall; "
          f"{decode['tokens_per_sec']:.1f} tok/s steady decode, "
          f"compile {decode['compile_s']:.1f}s)")
    engine.log_stats()
    obs.finalize(status="ok", requests=len(results), new_tokens=new,
                 decode_tokens_per_sec=decode["tokens_per_sec"])


def _serve_detector(args, obs) -> None:
    from repro.configs import yolo_irc
    from repro.data.detection import SyntheticDetectionData
    from repro.models.detector import IRCDetector
    from repro.serve import DetectorServeEngine

    cfg = yolo_irc.smoke(args.det_scheme)
    det = IRCDetector(cfg)
    params = det.init(jax.random.PRNGKey(0))
    data = SyntheticDetectionData(cfg.img_hw, cfg.n_classes, cfg.n_anchors,
                                  cfg.strides, seed=1)
    calib = data.batch_for_step(0, max(args.requests, 2))
    params = det.calibrate_bn(params, calib.images)

    # auto defers to the committed kernels/tuning.json; kernel forces the
    # Pallas chip-batched path (interpret mode on CPU)
    use_kernel = {"auto": None, "jnp": False, "kernel": True}[args.det_backend]
    engine = DetectorServeEngine(
        det, params, committee=args.committee, batch_slots=args.slots,
        max_queue=args.max_queue, seed=args.seed, use_kernel=use_kernel,
        obs=obs)

    images = np.asarray(calib.images)
    engine.start()
    t0 = time.time()
    rids = [engine.submit(images[i % images.shape[0]])
            for i in range(args.requests)]
    responses = [engine.result(rid, timeout=600) for rid in rids]
    dt = time.time() - t0
    engine.stop()

    for r in responses[:4]:
        c = r.confidence
        print(f"req {r.request_id} (wave {r.wave}): "
              f"{len(r.detections)} boxes, confidence "
              f"{c['mean']:.3f}±{c['std']:.3f} "
              f"[q05={c.get('q05', 0.0):.3f}, q95={c.get('q95', 0.0):.3f}], "
              f"queue {r.queue_s*1e3:.0f}ms")
    stats = engine.stats()
    lat = stats["queue_latency"]
    print(f"{len(responses)} requests over {args.committee}-chip committees, "
          f"{dt:.1f}s ({len(responses)/dt:.2f} req/s overall; "
          f"{stats['wave']['requests_per_sec']:.2f} req/s steady, "
          f"compile {stats['wave']['compile_s']:.1f}s; "
          f"queue p50={lat['p50']*1e3:.0f}ms p95={lat['p95']*1e3:.0f}ms)")
    engine.log_stats()
    obs.finalize(status="ok", requests=len(responses),
                 committee=args.committee,
                 requests_per_sec=stats["wave"]["requests_per_sec"],
                 queue_p50_s=lat["p50"], queue_p95_s=lat["p95"])


def main():
    """CLI entry: parse flags, open the run dir, route to the engine."""
    ap = _build_parser()
    args = ap.parse_args()
    _check_flag_use(ap, args)
    enable_compile_cache()

    from repro.obs import maybe_runlog
    name = ("serve-detector" if args.network == "detector"
            else f"serve-{args.arch}")
    obs = maybe_runlog(bool(args.run_dir), name, args=vars(args),
                       root=args.run_dir, run_id=args.run_id or None)
    if obs.path is not None:
        print(f"# run dir: {obs.path}")
    if args.trace:
        obs.start_trace()

    if args.network == "detector":
        _serve_detector(args, obs)
    else:
        _serve_lm(args, obs)


if __name__ == "__main__":
    main()
