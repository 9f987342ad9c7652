"""Nonideal-effect models of the IRC macro (paper Sec. III), as pure JAX ops.

Every effect is a vectorized function over crossbar quantities; the flags in
`NonidealConfig` correspond one-to-one to the columns of the paper's Table II
ablation (device variation / nonlinearity / nonideal peripheral circuits /
IR drop), so the ablation benchmark simply toggles them.

Conventions:
  - currents are in LRS units (1 unit == one ideal activated LRS cell);
  - `p` denotes the number of activated LRS cells on a bit-line;
  - per-cell quantities have shape [rows, cols_or_pairs]; batched bit-line
    quantities have shape [..., pairs].
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.macro import MacroSpec, DEFAULT_MACRO


@dataclasses.dataclass(frozen=True)
class NonidealConfig:
    """Which nonideal effects to simulate (Table II columns)."""

    device_variation: bool = False
    nonlinearity: bool = False
    sa_variation: bool = False       # "nonideal peripheral circuits" (1/2)
    sensing_range: bool = False      # "nonideal peripheral circuits" (2/2)
    ir_drop: bool = False

    @classmethod
    def none(cls) -> "NonidealConfig":
        """Ideal crossbar: every effect disabled."""
        return cls()

    @classmethod
    def all(cls) -> "NonidealConfig":
        """All five effects enabled (Table II "all nonideal" column)."""
        return cls(device_variation=True, nonlinearity=True, sa_variation=True,
                   sensing_range=True, ir_drop=True)

    def any(self) -> bool:
        """True when at least one effect is enabled."""
        return (self.device_variation or self.nonlinearity or self.sa_variation
                or self.sensing_range or self.ir_drop)


# ------------------------------------------------------------------ device variation

def sample_variation_mask(key: jax.Array, shape, sigma: float,
                          dtype=jnp.float32) -> jax.Array:
    """Per-cell multiplicative current mask for LRS cells.

    Measured LRS resistance is log-normal (Fig. 3, sigma ~= 0.4245 in log
    space at WL=0.44 V); cell current ~ 1/R is therefore log-normal with the
    same sigma. Median-preserving (median current = 1 unit), matching the
    paper's "multiply a random mask generated by the log-normal distribution"
    procedure.  HRS (non-formed, >1e9 ohm) variation is negligible (Sec. II-B)
    and is not masked.
    """
    z = jax.random.normal(key, shape, dtype=jnp.float32)
    return jnp.exp(sigma * z).astype(dtype)


# ------------------------------------------------------------------ nonlinearity

# Piecewise quartic fit of the accumulated bit-line current ratio vs the
# number of activated LRS cells p (Sec. III-C, exact published coefficients).
_NL_LO = (1.0286e-8, -3.79e-6, 5.3e-4, -3.92e-2, 2.5)        # p <= 140
_NL_HI = (1.8063e-11, -3.204e-8, 2.2495e-5, -8.057e-3, 1.707)  # p > 140


def _horner(p: jax.Array, coeffs) -> jax.Array:
    acc = jnp.full_like(p, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * p + c
    return acc


def nonlinearity_ratio(p: jax.Array) -> jax.Array:
    """ratio(p): accumulated-current / ideal-current for p activated LRS cells.

    ratio(0) is pinned to 1 (an empty bit-line has no current to distort);
    the paper's quartic fits cover p up to the 300 uA macro limit (~320
    LRS), so p is clamped to the fit domain — beyond it the quartics blow
    up unphysically.  The published piecewise fit has a small (~1.3%)
    discontinuity at the p=140 junction; we keep the exact coefficients for
    faithfulness.
    """
    p_raw = p.astype(jnp.float32)
    p = jnp.clip(p_raw, 0.0, 320.0)
    lo = _horner(p, _NL_LO)
    hi = _horner(p, _NL_HI)
    ratio = jnp.where(p <= 140.0, lo, hi)
    return jnp.where(p_raw < 0.5, 1.0, ratio)


def apply_nonlinearity(i_ideal: jax.Array, p: jax.Array) -> jax.Array:
    """Distort an accumulated bit-line current given its activated-LRS count."""
    return i_ideal * nonlinearity_ratio(p)


# ------------------------------------------------------------------ IR drop

def ir_drop_factors(block_currents: jax.Array, alpha: float,
                    axis: int = -1) -> jax.Array:
    """Per-block current-retention factors along a bit-line.

    `block_currents`: ideal current contributed by each 32-cell sub-block
    along `axis`, ordered with block 0 CLOSEST to the bit-line driver (paper
    Fig. 10).  The wire segment feeding block k carries the cumulative
    current of all blocks >= k, so the voltage seen by block b is reduced by
    alpha * sum_{k<=b} suffix_sum(I)_k; its cells lose current proportionally.
    Matches the paper's simplification: no intra-block IR drop, global
    block-to-block drop only (error <1% vs SPICE).

    The cumulative wire drop is LINEAR in the block currents:
      cum_wire[b] = sum_{k=1..b} suffix_sum(I)_k = sum_j min(b, j) * I_j,
    so here it is one [nb, nb] min-matrix contraction: Pallas TPU has no
    `rev`, and the fused kernel (`repro.kernels.irc_mvm`) and its oracle
    call this function on their [nb, bn] tile slabs.  This is the
    definition of the factors, and the path of a `repro.device` backend
    whose own `ir_drop_factors` hook replaces it.  The structural
    simulation on the analytic periphery does not materialize the factors:
    `ir_dropped_currents` computes the same sums by the suffix/prefix
    recurrence, in one pass over the block currents (on a v5e this dot at
    `HIGHEST` is six bf16 passes and moves several times the bytes of the
    block currents it weights).  The min matrix is symmetric, so each
    layout is a plain matmul.  The `axis` argument lets batched callers
    keep the block axis wherever their layout puts it.

    The contraction runs at `Precision.HIGHEST`: block currents carry
    variation-scaled conductances, and a default-precision TPU matmul would
    round them to bf16 — the same order as the variation being simulated.
    """
    nb = block_currents.shape[axis]
    row = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
    w_min = jnp.minimum(row, col).astype(jnp.float32)      # min(b, j)
    mm = lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    ax = axis % block_currents.ndim
    if ax == block_currents.ndim - 1:
        cum = mm(block_currents, w_min)
    elif ax == block_currents.ndim - 2:
        cum = mm(w_min, block_currents)
    else:
        moved = jnp.moveaxis(block_currents, ax, -1)
        cum = jnp.moveaxis(mm(moved, w_min), -1, ax)
    return jnp.clip(1.0 - alpha * cum, 0.0, 1.0)


def ir_dropped_currents(block_currents: jax.Array, alpha: float) -> list:
    """IR-dropped current of every block along a bit-line, in one pass.

    `block_currents` [..., nb, N] are the ideal per-block currents, block 0
    first, ordered as in `ir_drop_factors`.  Returns the nb [..., N]
    currents I_b * clip(1 - alpha * W_b, 0, 1), where the wire drop W_b is
    the cum_wire[b] that `ir_drop_factors` defines, built by a recurrence
    over the static block slices: the suffix current S_b = I_b + S_{b+1}
    from the far end, then W_0 = 0 and W_b = W_{b-1} + S_b.  Each term is
    float32 elementwise work on [..., N] slices, so XLA fuses the pass, and
    the sums its caller takes of the terms, into one loop that reads the
    block currents once; no [nb, nb] dot and no factor or cumulative
    tensor is materialized.  Only the order of the float32 additions
    differs from `block_currents * ir_drop_factors(block_currents)`.
    """
    nb = block_currents.shape[-2]
    cur = [block_currents[..., b, :] for b in range(nb)]
    suffix = cur[:]
    for b in range(nb - 2, -1, -1):
        suffix[b] = cur[b] + suffix[b + 1]
    out = [cur[0]]                         # W_0 = 0: block 0 keeps it all
    wire = None
    for b in range(1, nb):
        wire = suffix[b] if wire is None else wire + suffix[b]
        out.append(cur[b] * jnp.clip(1.0 - alpha * wire, 0.0, 1.0))
    return out


def apply_ir_drop(block_currents: jax.Array, alpha: float) -> jax.Array:
    """Total bit-line current after IR drop, from per-block ideal currents."""
    return jnp.sum(block_currents * ir_drop_factors(block_currents, alpha), axis=-1)


# ------------------------------------------------------------------ SA periphery

def sa_required_diff(p: jax.Array, spec: MacroSpec = DEFAULT_MACRO) -> jax.Array:
    """Required |I+ - I-| (units) for a correct SA decision, vs activated LRS
    count p on the compared pair (paper Fig. 9: grows with p)."""
    p = p.astype(jnp.float32)
    return spec.sa_c0 + spec.sa_c1 * p + spec.sa_c2 * p * p


def _device_or_analytic(device):
    """Resolve the `device=` seam: None -> the analytic singleton (imported
    lazily — `repro.device` depends on this module, not the reverse)."""
    if device is None:
        from repro.device import ANALYTIC_DEVICE
        return ANALYTIC_DEVICE
    return device


def sa_offset(key: jax.Array, p: jax.Array, spec: MacroSpec = DEFAULT_MACRO,
              extra_units: float = 0.0, device=None) -> jax.Array:
    """Random input-referred SA offset current (units).

    Modeled as zero-mean Gaussian whose sigma comes from the device model's
    periphery hook — analytic default: 2-sigma equals the required
    difference g(p) from Fig. 9 (+ `extra_units` for the Table IV tolerance
    sweep), i.e. decisions with |diff| >= g(p) are almost always correct.
    """
    sigma = _device_or_analytic(device).sa_offset_sigma(p, spec, extra_units)
    return sigma * jax.random.normal(key, p.shape, dtype=jnp.float32)


def sensing_failure(i_pos: jax.Array, i_neg: jax.Array,
                    spec: MacroSpec = DEFAULT_MACRO) -> jax.Array:
    """Boolean mask of unresolvable comparisons (limited sensing range).

    A bit-line outside [sense_low, sense_high] cannot be resolved within the
    sensing window (Sec. III-D): too little current fails to charge the
    parasitic bit-line capacitance in time, too much exceeds the design
    limit.  The extra common-mode bias rows (Sec. IV-B.4) exist precisely to
    lift `min(I+, I-)` above the lower bound.
    """
    lo = spec.sense_low_units
    hi = spec.sense_high_units
    too_low = jnp.minimum(i_pos, i_neg) < lo
    too_high = jnp.maximum(i_pos, i_neg) > hi
    return jnp.logical_or(too_low, too_high)


def resolve_sa(key: jax.Array, i_pos: jax.Array, i_neg: jax.Array,
               p_total: jax.Array, cfg: NonidealConfig,
               spec: MacroSpec = DEFAULT_MACRO,
               sa_extra_units: float = 0.0, device=None) -> jax.Array:
    """Binary SA decision in {0,1}: 1 iff (I+ - I- + offset) > 0, with
    unresolvable (out-of-range) comparisons replaced by random bits.
    `device` routes the offset sigma through a `repro.device` backend
    (None: analytic, bit-identical to the legacy inline formula)."""
    k_off, k_rng = jax.random.split(key)
    diff = i_pos - i_neg
    if cfg.sa_variation:
        diff = diff + sa_offset(k_off, p_total, spec, sa_extra_units, device)
    out = (diff > 0).astype(jnp.float32)
    if cfg.sensing_range:
        fail = sensing_failure(i_pos, i_neg, spec)
        rnd = jax.random.bernoulli(k_rng, 0.5, out.shape).astype(jnp.float32)
        out = jnp.where(fail, rnd, out)
    return out


def sensed_diff(key: jax.Array, i_pos: jax.Array, i_neg: jax.Array,
                p_total: jax.Array, cfg: NonidealConfig,
                spec: MacroSpec = DEFAULT_MACRO,
                sa_extra_units: float = 0.0, device=None) -> jax.Array:
    """Analog difference as ONE macro's periphery reports it to a digital
    combiner (multi-tile layers: each tile has its own SA front-end).

    Same key discipline and effect set as `resolve_sa`: the SA offset is an
    additive input-referred current, and an out-of-sensing-range comparison
    yields a garbage reading — modeled as a random-sign current at the
    just-resolvable magnitude g(p), so thresholding a SINGLE tile's sensed
    difference at zero reproduces `resolve_sa`'s decisions bit-for-bit.
    """
    k_off, k_rng = jax.random.split(key)
    diff = i_pos - i_neg
    if cfg.sa_variation:
        diff = diff + sa_offset(k_off, p_total, spec, sa_extra_units, device)
    if cfg.sensing_range:
        fail = sensing_failure(i_pos, i_neg, spec)
        rnd = jax.random.bernoulli(k_rng, 0.5, diff.shape)
        # the just-resolvable magnitude is 2x the offset sigma; the factor
        # of 2 * 0.5 is exact in floating point, so the analytic device
        # reproduces the historical g(p) + extra bit-for-bit
        mag = 2.0 * _device_or_analytic(device).sa_offset_sigma(
            p_total, spec, sa_extra_units)
        diff = jnp.where(fail, jnp.where(rnd, mag, -mag), diff)
    return diff
