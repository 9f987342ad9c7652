"""Ensemble QAT: the window drives the jitted `make_det_qat_step`.

Traffic parameters (`traffic/<mix>.json`, kind "qat"):

  effects          "all", "none" or {effect: bool}
  train_chips      dies drawn per step
  batch            images per step
  resample_every   steps between fresh die populations
  pool_batches     distinct batches rendered in set-up and cycled through
  lr               learning rate
  optimizer        AdamW settings {b1, b2, eps, weight_decay, grad_clip}
  calib_images     images `calibrate_bn` sees in set-up
  check            {number: limit} of the numbers compared (below)

Set-up builds the step and its state once and drives it through its first
three steps (the first compiles); the window goes on from step 3 with the
same object.  Step `s` takes batch `s mod pool_batches`, noise key
`fold_in(root, s)` and die key `fold_in(fold_in(root, 0x0E25),
s // resample_every)`, with root `fold_in(seed_key, 3)`.  At most two
steps are in flight.  `qat_step_ms` is the window's time over its steps.

The reference follows the first three steps on the same batches and keys.
Read: the first step's loss and each step's loss (relative gaps), the
first gradient as AdamW got it (from the first moment after step 1) and
the parameters' change after three steps.  A leaf's gap is the gap of the
two norms over the reference's norm of that leaf or of the median leaf,
whichever is larger; the gradient is read by its worst and its median
leaf, the change by its worst.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by weight decay and round-off alone
and are left out of the change.  The traffic's `check` names the numbers
compared; the others are printed beside them.
"""
from __future__ import annotations

import sys
import time
from typing import Dict

import numpy as np

import flops
import harness
import images
import reference

STEPS_CHECKED = 3
ENSEMBLE_STREAM = 0x0E25


def _leaves(tree):
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def leaf_gaps(got, ref, keep=None) -> np.ndarray:
    """Per kept leaf, |norm(got) - norm(ref)| / max(norm(ref), median
    leaf norm(ref))."""
    ng = np.array([np.linalg.norm(x) for x in got])
    nr = np.array([np.linalg.norm(x) for x in ref])
    keep = np.ones(len(nr), bool) if keep is None else keep
    floor = np.median(nr[keep])
    return np.abs(ng - nr)[keep] / np.maximum(nr[keep], floor)


def worst_leaf_gap(got, ref, keep=None) -> float:
    return float(np.max(leaf_gaps(got, ref, keep)))


class Qat:
    """The compiled step, its state and its feed."""

    def __init__(self, conf: Dict, traffic: Dict, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.optim import AdamWConfig, adamw_init
        from repro.train.steps import make_det_qat_step

        self.conf, self.traffic, self.seed = conf, traffic, seed
        net = conf["network"]
        det = harness.detector(conf)
        key = harness.seed_key(seed)
        self.root = jax.random.fold_in(key, 3)
        self.raw = harness.init_params(conf, jax.random.fold_in(key, 0))
        render = lambda n, stream: images.render_batch(
            tuple(net["img_hw"]), n, net["n_classes"], net["n_anchors"],
            det.cfg.strides, seed=(seed, stream))
        self.calib = render(traffic["calib_images"], 0)
        self.pool = [render(traffic["batch"], 10 + i)
                     for i in range(traffic["pool_batches"])]
        self.feed = [(jnp.asarray(b["images"]),
                      {k: jnp.asarray(v) for k, v in b["targets"].items()})
                     for b in self.pool]
        self.params = det.calibrate_bn(self.raw,
                                       jnp.asarray(self.calib["images"]))
        self.opt = adamw_init(self.params)
        self.lr = jnp.float32(traffic["lr"])
        self.step_fn = jax.jit(make_det_qat_step(
            det, train_chips=traffic["train_chips"],
            cfg_ni=harness.nonideal(harness.effects(traffic)),
            opt_cfg=AdamWConfig(**traffic["optimizer"])))
        self.steps = 0

    def keys(self, s: int):
        import jax
        ens = jax.random.fold_in(jax.random.fold_in(self.root,
                                                     ENSEMBLE_STREAM),
                                 s // self.traffic["resample_every"])
        return jax.random.fold_in(self.root, s), ens

    def step(self):
        """Dispatch the next step; returns its loss (not waited for)."""
        import jax
        s = self.steps
        imgs, targets = self.feed[s % len(self.feed)]
        with jax.profiler.TraceAnnotation("bench.qat_step"):
            key, ens = self.keys(s)
            self.params, self.opt, loss = self.step_fn(
                self.params, self.opt, imgs, targets, self.lr, key, ens)
        self.steps += 1
        return loss

    def first_steps(self):
        """Steps 0-2, with what the comparison needs of each."""
        start = _leaves(self.params)
        losses, grads = [], None
        for s in range(STEPS_CHECKED):
            losses.append(float(self.step()))
            if s == 0:
                b1 = self.traffic["optimizer"]["b1"]
                grads = [m / (1.0 - b1) for m in _leaves(self.opt["m"])]
        change = [a - b for a, b in zip(_leaves(self.params), start)]
        return {"losses": losses, "grads": grads, "change": change}

    def reference_steps(self, products: str = "exact"):
        """The reference's first three steps on the same feed and keys."""
        import jax
        phys = reference.Physics(self.conf)
        params = reference.calibrated(phys, self.raw, self.calib["images"])
        state = reference.adamw_init(params)
        start = _leaves(params)
        opt = tuple(sorted(self.traffic["optimizer"].items()))
        eff = reference.effects_tuple(harness.effects(self.traffic))
        losses, grads = [], None
        for s in range(STEPS_CHECKED):
            imgs, targets = self.feed[s % len(self.feed)]
            key, ens = self.keys(s)
            params, state, loss = reference.train_step(
                params, state, imgs, targets, self.lr, key, ens, phys=phys,
                effects=eff, chips=self.traffic["train_chips"], opt=opt,
                products=products)
            losses.append(float(loss))
            if s == 0:
                b1 = self.traffic["optimizer"]["b1"]
                grads = [m / (1.0 - b1) for m in _leaves(state[0])]
        change = [a - b for a, b in zip(_leaves(params), start)]
        return {"losses": losses, "grads": grads, "change": change}


def compare(got: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers read, from program and reference readings; the traffic's
    `check` names those compared."""
    norms = np.array([np.linalg.norm(g) for g in ref["grads"]])
    moved = norms >= 1e-3 * np.median(norms)
    gaps = [abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                 ref["losses"])]
    return {"loss0_gap": float(gaps[0]), "loss_gap": float(max(gaps)),
            "grad_gap": worst_leaf_gap(got["grads"], ref["grads"]),
            "grad_median_gap": float(np.median(leaf_gaps(got["grads"],
                                                         ref["grads"]))),
            "change_gap": worst_leaf_gap(got["change"], ref["change"],
                                         moved)}


def run(run) -> Dict:
    q = Qat(run.conf, run.traffic, run.seed)
    got = q.first_steps()
    run.setup_done()
    failed = 0
    with run.window():
        t0 = time.perf_counter()
        first = q.steps
        inflight = []
        while time.perf_counter() - t0 < run.seconds:
            inflight.append(q.step())
            if len(inflight) > 2:
                failed += int(not np.isfinite(float(inflight.pop(0))))
        for loss in inflight:
            failed += int(not np.isfinite(float(loss)))
        elapsed = time.perf_counter() - t0
        steps = q.steps - first
    run.read_memory()
    del q.params, q.opt
    numbers = compare(got, q.reference_steps())
    print("numbers read:", numbers, file=sys.stderr)
    for name, limit in run.traffic["check"].items():
        run.check(name, numbers[name], limit)
    run.counters.update(
        steps=steps, window_s=elapsed,
        step_flops=flops.qat_step_flops(run.conf,
                                        run.traffic["train_chips"],
                                        run.traffic["batch"]))
    return {"attempted": steps, "failed": failed,
            "e2e": {"qat_step_ms": 1e3 * elapsed / steps}}


def readings(conf: Dict, traffic: Dict, seed: int, control: bool) -> Dict:
    """The numbers compared, for setting limits: the program's first three
    steps against the reference's; with `control`, the reference at the
    precision below the configuration's, and the reference on half of each
    batch (the mean over the rest), against it.  A step that returns its
    state unchanged reads change_gap 1 by definition."""
    q = Qat(conf, traffic, seed)
    got = q.first_steps()
    del q.params, q.opt
    ref = q.reference_steps()
    out = compare(got, ref)
    if control:
        out.update({f"control.{k}": v for k, v in
                    compare(q.reference_steps("three_pass"), ref).items()})
        half = traffic["batch"] // 2
        q.feed = [(i[:half], {k: v[:half] for k, v in t.items()})
                  for i, t in q.feed]
        out.update({f"half_batch.{k}": v for k, v in
                    compare(q.reference_steps(), ref).items()})
    return out
