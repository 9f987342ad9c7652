"""Distributed training launcher.

On a TPU fleet each host runs this entry point (jax.distributed handles the
cross-host runtime); on this CPU container it runs the same code path on the
host mesh.  Fault tolerance is built in: resume-from-latest checkpoint,
stateless-seeded data (restart-exact), async keep-k saves, straggler
logging.  Elastic restart: if the mesh shape changed since the checkpoint
(node failure -> smaller pool), restore reshards against the new mesh.

  PYTHONPATH=src python -m repro.launch.train --arch hymba-1.5b \
      --variant smoke --steps 50 --batch 8 --seq 128
  (production: --mesh single|multi on a real 256/512-chip fleet)
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.registry import get_config, list_archs
from repro.data import SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LM
from repro.models.lm_config import IRCMode
from repro.optim import AdamWConfig
from repro.sharding.rules import tree_pspecs
from repro.train import make_train_step
from repro.train.steps import init_train_state, train_state_axes
from repro.train.trainer import Trainer, TrainerConfig


def build_mesh(kind: str):
    if kind in ("single", "multi"):
        from repro.launch.mesh import make_production_mesh
        return make_production_mesh(multi_pod=(kind == "multi"))
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba-1.5b", choices=list_archs())
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "block", "dots", "names"])
    ap.add_argument("--irc", action="store_true",
                    help="ternary-QAT every projection (the paper's mode)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--weight-decay", type=float, default=1e-3)
    ap.add_argument("--run-dir", default="",
                    help="experiments/<run_id>/ run directory root "
                         "(manifest + metrics.jsonl; '' disables)")
    ap.add_argument("--run-id", default="")
    ap.add_argument("--trace", action="store_true",
                    help="capture a jax.profiler trace into the run dir")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.obs import maybe_runlog
    obs = maybe_runlog(bool(args.run_dir), f"train-{args.arch}",
                       args=vars(args), root=args.run_dir,
                       run_id=args.run_id or None)
    if obs.path is not None:
        print(f"# run dir: {obs.path}")
    if args.trace:
        obs.start_trace()

    cfg = get_config(args.arch, args.variant)
    if args.irc:
        cfg = dataclasses.replace(cfg, irc=IRCMode(enabled=True))
    mesh = build_mesh(args.mesh)
    lm = LM(cfg)
    if mesh.devices.size > 1:
        lm.use_mesh(mesh)

    state = init_train_state(lm, jax.random.PRNGKey(0))
    if mesh.devices.size > 1:
        shardings = jax.tree.map(
            lambda p: NamedSharding(mesh, p),
            tree_pspecs(train_state_axes(lm), jax.eval_shape(lambda: state),
                        mesh),
            is_leaf=lambda x: hasattr(x, "index_sizes") or
            type(x).__name__ == "PartitionSpec")
        state = jax.device_put(state, shardings)

    data = SyntheticLMData(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           global_batch=args.batch)
    step_fn = make_train_step(
        lm, opt_cfg=AdamWConfig(weight_decay=args.weight_decay),
        lr_fn=lambda s: jnp.float32(args.lr),
        remat=args.remat, microbatch=args.microbatch)
    trainer = Trainer(
        TrainerConfig(total_steps=args.steps,
                      ckpt_every=max(args.steps // 4, 1),
                      ckpt_dir=args.ckpt_dir,
                      log_every=max(args.steps // 20, 1)),
        step_fn, lambda s: data.batch_for_step(s), state, obs=obs)
    hist = trainer.run()
    print(f"final loss {hist[-1]['loss']:.4f} over {len(hist)} steps "
          f"(resumed at {hist[0]['step']}); "
          f"stragglers: {len(trainer.straggler_steps)}; "
          f"compile {trainer.step_timer.compile_s:.1f}s, "
          f"{trainer.step_timer.rate():.2f} steps/s steady")
    obs.finalize(status="ok", final_loss=hist[-1]["loss"],
                 steps=len(hist),
                 steps_per_sec=trainer.step_timer.rate(),
                 compile_s=trainer.step_timer.compile_s)


if __name__ == "__main__":
    main()
