"""Pure-jnp oracles for the Pallas kernels.

`irc_mvm_ref` mirrors `repro.kernels.irc_mvm` exactly: the proposed design's
single-shot crossbar MVM with the fused nonideal epilogue.  Conductance
planes arrive with device variation and HRS leak PRE-APPLIED (programming a
chip is static; masks are sampled once per simulated die, outside the MVM),
and the stochastic periphery terms arrive as externally sampled noise so the
kernel itself is deterministic and exactly testable.

`ternary_matmul_ref` is the ideal digital path: {0,1} activations x int8
ternary weights.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.nonideal import ir_drop_factors

# physics contractions run in full f32 on every backend (a default-precision
# TPU matmul rounds its f32 operands to bf16)
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class IrcEpilogueParams:
    """Static epilogue constants (from MacroSpec, in LRS units)."""
    ir_alpha: float = 1.5e-5
    ir_block: int = 32
    sense_low: float = 35.0
    sense_high: float = 300.0
    sa_c0: float = 2.0
    sa_c1: float = 0.012
    sa_c2: float = 2.2e-5
    sa_extra: float = 0.0
    apply_nonlinearity: bool = True
    apply_ir: bool = True
    apply_sa: bool = True
    apply_range: bool = True
    output: str = "binary"            # "binary" | "diff"

    @classmethod
    def from_macro(cls, spec, **overrides) -> "IrcEpilogueParams":
        kw = dict(ir_alpha=spec.ir_alpha, ir_block=spec.ir_block,
                  sense_low=spec.sense_low_units, sense_high=spec.sense_high_units,
                  sa_c0=spec.sa_c0, sa_c1=spec.sa_c1, sa_c2=spec.sa_c2)
        kw.update(overrides)
        return cls(**kw)


# exact published piecewise quartic (Sec. III-C), clamped to fit domain
_NL_LO = (1.0286e-8, -3.79e-6, 5.3e-4, -3.92e-2, 2.5)
_NL_HI = (1.8063e-11, -3.204e-8, 2.2495e-5, -8.057e-3, 1.707)


def nl_ratio(p: jax.Array) -> jax.Array:
    p_raw = p.astype(jnp.float32)
    p = jnp.clip(p_raw, 0.0, 320.0)
    def horner(c):
        acc = jnp.full_like(p, c[0])
        for x in c[1:]:
            acc = acc * p + x
        return acc
    ratio = jnp.where(p <= 140.0, horner(_NL_LO), horner(_NL_HI))
    return jnp.where(p_raw < 0.5, 1.0, ratio)


def _line_current(x: jax.Array, eplane: jax.Array, ep_: IrcEpilogueParams
                  ) -> jax.Array:
    """Accumulate one plane with the IR-drop block model.
    x [B,R], eplane [R,N] -> [B,N].  R is padded up to a multiple of the IR
    block size; appended zero rows sit at the far end of the bit-line and
    carry no current, so the drop factors of real blocks are unchanged.
    The drop factors come from `repro.core.nonideal.ir_drop_factors`, the
    definition the kernel also uses (the structural simulation computes the
    same sums in one pass, `ir_dropped_currents`)."""
    pad = (-x.shape[1]) % ep_.ir_block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        eplane = jnp.pad(eplane, ((0, pad), (0, 0)))
    B, R = x.shape
    N = eplane.shape[1]
    nb = R // ep_.ir_block
    xb = x.reshape(B, nb, ep_.ir_block)
    pb = eplane.reshape(nb, ep_.ir_block, N)
    blocks = jnp.einsum("bik,ikn->bin", xb, pb,
                        precision=_HIGHEST)               # [B, nb, N]
    if ep_.apply_ir:
        blocks = blocks * ir_drop_factors(blocks, ep_.ir_alpha, axis=-2)
    return jnp.sum(blocks, axis=1)


def irc_mvm_ref(x: jax.Array, ep: jax.Array, en: jax.Array,
                gp: jax.Array, gn: jax.Array,
                eps_sa: jax.Array, rnd_bits: jax.Array,
                params: IrcEpilogueParams) -> jax.Array:
    """Oracle for the fused IRC MVM kernel.

    x        [B, R]  word-line bits {0,1} (bias rows already prefixed)
    ep, en   [R, N]  effective conductances (variation/leak pre-applied)
    gp, gn   [R, N]  binary LRS placement planes (for activated-LRS counts)
    eps_sa   [B, N]  ~N(0,1) SA offset noise
    rnd_bits [B, N]  {0,1} fallback bits for unresolvable comparisons
    """
    x = x.astype(jnp.float32)
    i_pos = _line_current(x, ep.astype(jnp.float32), params)
    i_neg = _line_current(x, en.astype(jnp.float32), params)
    p_pos = jnp.matmul(x, gp.astype(jnp.float32), precision=_HIGHEST)
    p_neg = jnp.matmul(x, gn.astype(jnp.float32), precision=_HIGHEST)
    if params.apply_nonlinearity:
        i_pos = i_pos * nl_ratio(p_pos)
        i_neg = i_neg * nl_ratio(p_neg)
    diff = i_pos - i_neg
    if params.output == "diff":
        return diff
    p_pair = p_pos + p_neg
    if params.apply_sa:
        sigma = 0.5 * (params.sa_c0 + params.sa_c1 * p_pair
                       + params.sa_c2 * p_pair * p_pair + params.sa_extra)
        diff = diff + sigma * eps_sa
    out = (diff > 0).astype(jnp.float32)
    if params.apply_range:
        fail = jnp.logical_or(jnp.minimum(i_pos, i_neg) < params.sense_low,
                              jnp.maximum(i_pos, i_neg) > params.sense_high)
        out = jnp.where(fail, rnd_bits, out)
    return out


def irc_mvm_chips_ref(x: jax.Array, ep: jax.Array, en: jax.Array,
                      gp: jax.Array, gn: jax.Array,
                      eps_sa: jax.Array, rnd_bits: jax.Array,
                      params: IrcEpilogueParams) -> jax.Array:
    """Oracle for the chip-batched kernel: vmap of `irc_mvm_ref` over the
    leading chips axis of the planes / periphery noise.

    x [B, R] (shared word lines) or [C, B, R] (per-chip word-line stream);
    ep/en [C, R, N]; gp/gn [C, R, N] or shared [R, N];
    eps/rnd [C, B, N] -> [C, B, N]."""
    count_axis = None if gp.ndim == 2 else 0
    x_axis = None if x.ndim == 2 else 0
    return jax.vmap(
        lambda x_c, ep_c, en_c, gp_c, gn_c, eps_c, rnd_c: irc_mvm_ref(
            x_c, ep_c, en_c, gp_c, gn_c, eps_c, rnd_c, params),
        in_axes=(x_axis, 0, 0, count_axis, count_axis, 0, 0)
    )(x, ep, en, gp, gn, eps_sa, rnd_bits)


def ternary_matmul_ref(x: jax.Array, w_t: jax.Array) -> jax.Array:
    """Ideal digital ternary matmul oracle: x [B,K] (any float), w_t [K,N]
    int8 in {-1,0,1} -> f32 [B,N]."""
    return x.astype(jnp.float32) @ w_t.astype(jnp.float32)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """Oracle for the flash kernel: plain softmax attention.
    q [H,Sq,hd], k/v [H,Sk,hd] -> [H,Sq,hd]."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = s.shape[-2:]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
