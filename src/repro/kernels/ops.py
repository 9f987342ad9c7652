"""Public jit'd entry points for the Pallas kernels (padding + dispatch).

On CPU the kernels execute in interpret mode; on TPU they compile to
Mosaic.  The choice follows the platform the call is traced for — the
`jax.default_device` override when one is set (it is part of the jit key),
else the default backend — so a TPU host can run the same call on its CPU
device for a cross-check.  Shapes are padded to tile multiples here so
callers can pass arbitrary layer shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# re-exported: ops is the backend-dispatch facade over the ref kernels
from repro.kernels.ref import (IrcEpilogueParams, irc_mvm_ref,  # noqa: F401
                               ternary_matmul_ref)
from repro.kernels.irc_mvm import irc_mvm_pallas, irc_mvm_chips_pallas
from repro.kernels.ternary_matmul import ternary_matmul_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def target_platform() -> str:
    """Platform the computation being traced will run on."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def _on_cpu() -> bool:
    return target_platform() == "cpu"


def _pad_to(a: jax.Array, axis: int, mult: int) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit, static_argnames=("params", "bm", "bn", "bk",
                                             "interpret"))
def irc_mvm(x: jax.Array, ep: jax.Array, en: jax.Array,
            gp: jax.Array, gn: jax.Array,
            eps_sa: jax.Array, rnd_bits: jax.Array,
            params: IrcEpilogueParams,
            bm: int = 8, bn: int = 128, bk: int = 256,
            interpret: Optional[bool] = None) -> jax.Array:
    """Fused single-shot IRC crossbar MVM (see irc_mvm.py docstring).

    Accepts arbitrary (B, R, N); pads to tile multiples.  Padded rows are
    zero-conductance (contribute no current, no counts), padded batch/cols
    are sliced off.
    """
    B, R = x.shape
    N = ep.shape[1]
    interp = _on_cpu() if interpret is None else interpret
    x = _pad_to(_pad_to(x, 0, bm), 1, bk)
    pad_plane = lambda p: _pad_to(_pad_to(p, 0, bk), 1, bn)
    ep, en, gp, gn = map(pad_plane, (ep, en, gp, gn))
    pad_bn = lambda p: _pad_to(_pad_to(p, 0, bm), 1, bn)
    eps_sa, rnd_bits = map(pad_bn, (eps_sa, rnd_bits))
    out = irc_mvm_pallas(x, ep, en, gp, gn, eps_sa, rnd_bits, params,
                         bm=bm, bn=bn, bk=bk, interpret=interp)
    return out[:B, :N]


@functools.partial(jax.jit, static_argnames=("params", "bm", "bn", "bk",
                                             "interpret"))
def irc_mvm_chips(x: jax.Array, ep: jax.Array, en: jax.Array,
                  gp: jax.Array, gn: jax.Array,
                  eps_sa: jax.Array, rnd_bits: jax.Array,
                  params: IrcEpilogueParams,
                  bm: int = 8, bn: int = 128, bk: int = 256,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Chip-batched fused IRC MVM: x [B,R] shared (or [C,B,R] per-chip
    word-line stream), effective planes [C,R,N], placement planes [C,R,N] or
    shared [R,N], periphery noise [C,B,N] -> [C,B,N] in ONE kernel launch
    (the `repro.mc` hot path).

    Accepts arbitrary (C, B, R, N); pads B/R/N to tile multiples (padded rows
    are zero-conductance, padded batch/cols are sliced off; the chips axis
    needs no padding — it maps 1:1 onto the outermost grid dimension).
    """
    B, R = x.shape[-2:]
    C, _, N = ep.shape
    interp = _on_cpu() if interpret is None else interpret
    x = _pad_to(_pad_to(x, x.ndim - 2, bm), x.ndim - 1, bk)
    pad_plane = lambda p: _pad_to(_pad_to(p, p.ndim - 2, bk), p.ndim - 1, bn)
    ep, en, gp, gn = map(pad_plane, (ep, en, gp, gn))
    pad_bn = lambda p: _pad_to(_pad_to(p, 1, bm), 2, bn)
    eps_sa, rnd_bits = map(pad_bn, (eps_sa, rnd_bits))
    out = irc_mvm_chips_pallas(x, ep, en, gp, gn, eps_sa, rnd_bits, params,
                               bm=bm, bn=bn, bk=bk, interpret=interp)
    return out[:, :B, :N]


def irc_mvm_from_mapped(key: jax.Array, x_bits: jax.Array, mapped,
                        cfg, spec, *, sa_extra_units: float = 0.0,
                        output: str = "binary",
                        bm: int = 8, bn: int = 128, bk: int = 256) -> jax.Array:
    """Kernel-backed equivalent of `repro.core.crossbar.crossbar_forward`
    (single-shot mode): samples the variation masks / SA noise with the SAME
    key discipline, pre-applies them to the conductance planes, and calls the
    fused kernel.  Bit-exact agreement is covered by tests/test_kernels.py.
    """
    from repro.core.mapping import extend_inputs
    from repro.core.crossbar import sample_chip_planes
    gp, gn = mapped.g_pos, mapped.g_neg
    ep, en, k_sa = sample_chip_planes(key, gp, gn, mapped.scheme, cfg, spec)
    k_off, k_rng = jax.random.split(k_sa)
    x_ext = extend_inputs(x_bits.astype(jnp.float32), mapped)
    B, N = x_ext.shape[0], gp.shape[1]
    eps_sa = jax.random.normal(k_off, (B, N), jnp.float32)
    rnd = jax.random.bernoulli(k_rng, 0.5, (B, N)).astype(jnp.float32)
    params = IrcEpilogueParams.from_macro(
        spec, sa_extra=sa_extra_units, output=output,
        apply_nonlinearity=cfg.nonlinearity, apply_ir=cfg.ir_drop,
        apply_sa=cfg.sa_variation, apply_range=cfg.sensing_range)
    return irc_mvm(x_ext, ep, en, gp, gn, eps_sa, rnd, params,
                   bm=bm, bn=bn, bk=bk)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 512, bk: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Causal flash attention: q [H,Sq,hd], k/v [H,Sk,hd] -> [H,Sq,hd].
    Sequences are zero-padded to block multiples; with causal masking the
    padded KV tail can never attend into real queries.  vmap over batch."""
    assert causal, "public wrapper supports the causal case"
    H, Sq, hd = q.shape
    Sk = k.shape[1]
    interp = _on_cpu() if interpret is None else interpret
    bq_ = min(bq, Sq) if Sq % min(bq, Sq) == 0 else Sq
    bk_ = min(bk, Sk) if Sk % min(bk, Sk) == 0 else Sk
    qp = _pad_to(q, 1, bq_)
    kp = _pad_to(k, 1, bk_)
    vp = _pad_to(v, 1, bk_)
    out = flash_attention_pallas(qp, kp, vp, causal=True, bq=bq_, bk=bk_,
                                 interpret=interp)
    return out[:, :Sq]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ternary_matmul(x: jax.Array, w_t: jax.Array,
                   bm: int = 128, bn: int = 128, bk: int = 512,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Dense ternary matmul with int8-packed weights."""
    B, K = x.shape
    N = w_t.shape[1]
    interp = _on_cpu() if interpret is None else interpret
    x = _pad_to(_pad_to(x, 0, bm), 1, bk)
    w_t = _pad_to(_pad_to(w_t, 0, bk), 1, bn)
    out = ternary_matmul_pallas(x, w_t, bm=bm, bn=bn, bk=bk, interpret=interp)
    return out[:B, :N]
