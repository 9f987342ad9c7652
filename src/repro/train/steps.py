"""jit-able train / eval / decode steps shared by the trainer, the serving
engine, and the multi-pod dry-run.

`make_train_step(lm, ...)` returns a pure function
    (state, batch) -> (state, metrics)
with loss+grad under remat, global-norm clipping, AdamW, and the paper's LR
schedule; everything pjit-shards via the in/out shardings the caller derives
from `repro.sharding.rules`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.transformer import LM
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         warmup_step_decay)

PyTree = Any


@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt: PyTree
    step: jax.Array


jax.tree_util.register_pytree_with_keys(
    TrainState,
    lambda s: ((("params", s.params), ("opt", s.opt), ("step", s.step)),
               None),
    lambda aux, c: TrainState(*c))


def init_train_state(lm: LM, key: jax.Array) -> TrainState:
    params = lm.init(key)
    return TrainState(params=params, opt=adamw_init(params),
                      step=jnp.zeros((), jnp.int32))


def abstract_train_state(lm: LM) -> TrainState:
    """ShapeDtypeStruct TrainState (no allocation) for AOT lowering."""
    params = lm.abstract_params()
    opt = jax.eval_shape(adamw_init, params)
    return TrainState(params=params, opt=opt,
                      step=jax.ShapeDtypeStruct((), jnp.int32))


def train_state_axes(lm: LM) -> TrainState:
    """Logical-axes TrainState matching abstract_train_state (moments share
    the param sharding; step is replicated)."""
    axes = lm.logical_axes()
    return TrainState(
        params=axes,
        opt={"m": axes, "v": axes, "step": ()},
        step=())


def make_train_step(lm: LM, *, opt_cfg: AdamWConfig = AdamWConfig(),
                    lr_fn: Optional[Callable] = None, remat: str = "block",
                    microbatch: int = 1, scan_layers: bool = True,
                    scan_microbatches: bool = True
                    ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                  Tuple[TrainState, Dict[str, jax.Array]]]:
    """scan_microbatches=False unrolls the grad-accumulation loop — used by
    the roofline cost probes (XLA cost_analysis counts a scanned microbatch
    body once regardless of trip count)."""
    lr_fn = lr_fn or (lambda s: warmup_step_decay(s))

    def loss_fn(params, batch):
        return lm.loss(params, batch, remat=remat, scan_layers=scan_layers)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        if microbatch > 1:
            # gradient accumulation over leading micro-slices of the batch
            def micro(carry, mb):
                g_acc, l_acc = carry
                (l, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), metrics

            mb_batch = jax.tree.map(
                lambda x: x.reshape((microbatch, x.shape[0] // microbatch)
                                    + x.shape[1:]), batch)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            if scan_microbatches:
                (grads, loss_sum), metrics = jax.lax.scan(
                    micro, (zeros, jnp.zeros((), jnp.float32)), mb_batch)
                metrics = jax.tree.map(lambda m: m[-1], metrics)
            else:
                carry = (zeros, jnp.zeros((), jnp.float32))
                for i in range(microbatch):
                    carry, metrics = micro(
                        carry, jax.tree.map(lambda x: x[i], mb_batch))
                grads, loss_sum = carry
            grads = jax.tree.map(lambda g: g / microbatch, grads)
            loss = loss_sum / microbatch
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        lr = lr_fn(state.step)
        new_params, new_opt, opt_metrics = adamw_update(
            grads, state.opt, state.params, lr, opt_cfg)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["lr"] = lr
        metrics["loss"] = loss
        new_state = TrainState(params=new_params, opt=new_opt,
                               step=state.step + 1)
        return new_state, metrics

    return train_step


# ------------------------------------------------------------- detector QAT

# Salt separating the chip-population key stream from the per-step noise
# stream (`fold_in(root, step)`), so one root key reproduces a whole QAT run.
ENSEMBLE_KEY_STREAM = 0x0E25


def ensemble_key_for_step(key: jax.Array, step: int,
                          resample_every: int = 1) -> jax.Array:
    """Chip-population key for QAT step `step`.

    Advances every `resample_every` steps: within a window the population's
    variation masks are FROZEN (the same dies are seen while their planes are
    rebuilt from the current quantized weights each step), and the dies are
    resampled exactly on schedule.
    """
    assert resample_every >= 1, resample_every
    return jax.random.fold_in(jax.random.fold_in(key, ENSEMBLE_KEY_STREAM),
                              step // resample_every)


def make_det_qat_step(det, *, train_chips: int = 1,
                      cfg_ni=None,
                      opt_cfg: AdamWConfig = AdamWConfig(weight_decay=1e-3)
                      ) -> Callable:
    """Build the detector QAT step shared by `quick_qat`, the MC CLI and the
    paper-scale driver:

        (params, opt, images, targets, lr, key, ens_key)
            -> (params, opt, loss)

    `train_chips=1` (default) is EXACTLY the legacy single-draw step — loss
    through `mode="train"` with one surrogate-noise draw keyed `key`;
    `ens_key` is ignored.  Bit-identity with the historical `quick_qat` step
    is a guarantee (tests pin it).

    `train_chips>=2` is ensemble-aware QAT (paper Sec. V at population
    scale): the step draws a `train_chips` deviation population keyed
    `ens_key` (`repro.mc.build_train_ensemble` — planes from the CURRENT
    quantized weights, chip identity frozen between `ens_key` changes), runs
    `mode="train_ensemble"`, and averages the loss over chip realizations by
    folding the chips axis into the batch.

    The step's phases run under named scopes that a device trace carries
    in each op's `tf_op`: `train_planes` (the deviation population), the
    detector's own (`stem`, `s{s}b{b}`, `s{s}pool`, `head`), `loss` and
    `adamw`; the backward pass of each appears as `transpose(jvp(<scope>))`.
    """
    from repro.core import nonideal as ni
    from repro.train.det_loss import yolo_loss
    if train_chips < 1:
        raise ValueError(f"train_chips must be >= 1, got {train_chips}")
    cfg_ni = ni.NonidealConfig.none() if cfg_ni is None else cfg_ni

    def qat_step(params, opt, images, targets, lr, key, ens_key):
        def loss_fn(p):
            if train_chips == 1:
                pred = det.apply(p, images, mode="train", key=key,
                                 cfg_ni=cfg_ni)
                with jax.named_scope("loss"):
                    return yolo_loss(pred, targets, det.cfg.n_anchors,
                                     det.cfg.n_classes)
            from repro.mc.detector_mc import build_train_ensemble
            with jax.named_scope("train_planes"):
                ens = build_train_ensemble(ens_key, det, p, train_chips,
                                           cfg=cfg_ni)
            pred = det.apply(p, images, mode="train_ensemble", key=key,
                             cfg_ni=cfg_ni, ensemble=ens)
            with jax.named_scope("loss"):
                pred = pred.reshape((-1,) + pred.shape[2:])  # chips to batch
                tiled = jax.tree.map(
                    lambda t: jnp.tile(t, (train_chips,)
                                       + (1,) * (t.ndim - 1)),
                    targets)
                return yolo_loss(pred, tiled, det.cfg.n_anchors,
                                 det.cfg.n_classes)
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("adamw"):
            params, opt, _ = adamw_update(grads, opt, params, lr, opt_cfg)
        return params, opt, loss

    return qat_step


def make_eval_step(lm: LM) -> Callable:
    def eval_step(params, batch):
        _, metrics = lm.loss(params, batch, remat="none")
        return metrics
    return eval_step


def make_decode_step(lm: LM) -> Callable:
    def decode_step(params, tokens, cache):
        return lm.decode_step(params, tokens, cache)
    return decode_step
