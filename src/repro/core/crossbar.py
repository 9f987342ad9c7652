"""Crossbar forward simulation + IRC layer modules (the paper's core).

Two execution paths, mirroring the paper's methodology:

  * `crossbar_forward` — the full structural simulation used at INFERENCE /
    evaluation time: conductance planes, per-cell device variation, 32-cell
    IR-drop blocks, accumulation nonlinearity (single-shot vs partial-sum),
    SA offset + limited sensing range.  This is the function the Pallas
    kernel (`repro.kernels.irc_mvm`) accelerates.
  * `irc_linear_train` — the differentiable surrogate used for QAT /
    "retraining": ideal ternary matmul + reparametrized noise matching the
    first-order statistics of the structural sim, with STE quantizers.

Accumulation modes (Sec. III-C / IV-B.3):
  * "single_shot": the whole column accumulates analog in one operation
    (proposed; enabled by the lowered word-line voltage).  The monotone
    nonlinearity then cancels in the differential comparison.
  * "partial_sum": the column is split into `partial_rows`-row chunks whose
    currents are accumulated externally (baseline; forced by the 300 uA
    bit-line limit at nominal word-line voltage).  Each chunk sees its own
    nonlinearity, which does NOT cancel.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.macro import MacroSpec, DEFAULT_MACRO
from repro.core import nonideal as ni
from repro.core.mapping import MappedLayer, extend_inputs
from repro.core.ternary import (ternary_quantize, binary_quantize,
                                binary_activation, soft_sa_output)


# ------------------------------------------------------------------ structural sim

def _block_reduce(x_ext: jax.Array, plane: jax.Array, block: int
                  ) -> jax.Array:
    """Per-IR-block partial currents: x_ext [..., R], plane [R, N]
    -> [..., nb, N] with nb = ceil(R / block).  Full f32 (`HIGHEST`): the
    planes carry variation-scaled conductances that a default-precision
    TPU matmul would round to bf16."""
    rows, n_out = plane.shape
    nb = -(-rows // block)
    pad = nb * block - rows
    if pad:
        x_ext = jnp.pad(x_ext, [(0, 0)] * (x_ext.ndim - 1) + [(0, pad)])
        plane = jnp.pad(plane, ((0, pad), (0, 0)))
    xb = x_ext.reshape(x_ext.shape[:-1] + (nb, block))
    pb = plane.reshape(nb, block, n_out)
    return jnp.einsum("...bk,bkn->...bn", xb, pb,
                      precision=jax.lax.Precision.HIGHEST)


def _chunk_bounds(nb: int, accumulation: str, partial_rows: int,
                  block: int) -> Tuple[Tuple[int, int], ...]:
    """Static (lo, hi) block ranges whose currents accumulate together:
    the whole line for "single_shot"; runs of `partial_rows // block`
    blocks for "partial_sum", the last one short where they do not divide
    nb."""
    if accumulation == "single_shot":
        return ((0, nb),)
    if accumulation == "partial_sum":
        k = max(1, partial_rows // block)
        return tuple((lo, min(lo + k, nb)) for lo in range(0, nb, k))
    raise ValueError(f"unknown accumulation mode: {accumulation}")


def _accumulate(blocks: jax.Array, counts: jax.Array, cfg: ni.NonidealConfig,
                spec: MacroSpec, accumulation: str, partial_rows: int,
                device=None) -> Tuple[jax.Array, jax.Array]:
    """Apply IR drop + nonlinearity to per-block currents.

    blocks/counts: [..., nb, N] (currents with variation / ideal LRS counts).
    Returns (bit-line current [..., N], activated LRS count [..., N]).

    With IR drop on the analytic periphery (`device` None or one whose
    `analytic_periphery` holds) the dropped currents of the line or of
    each partial-sum chunk come from `ni.ir_dropped_currents`, one pass over
    the blocks under the `ir_drop` scope.  A backend with its own periphery
    weights the blocks by its `ir_drop_factors` hook instead.  Chunk c of a
    partial sum is blocks [c*k, min((c+1)*k, nb)), k = partial_rows //
    ir_block; each chunk, or the single-shot line, then sees the
    nonlinearity of its own activated-LRS count.
    """
    chunks = _chunk_bounds(blocks.shape[-2], accumulation, partial_rows,
                           spec.ir_block)
    dev = ni._device_or_analytic(device)
    p_total = jnp.sum(counts, axis=-2)
    p_chunks = [p_total]
    if cfg.nonlinearity and len(chunks) > 1:
        p_chunks = [_block_sum(counts, lo, hi) for lo, hi in chunks]
    if not (cfg.ir_drop and dev.analytic_periphery):
        if cfg.ir_drop:
            blocks = blocks * dev.ir_drop_factors(blocks, spec, axis=-2)
        if len(chunks) == 1:
            i_chunks = [jnp.sum(blocks, axis=-2)]
        else:
            i_chunks = [_block_sum(blocks, lo, hi) for lo, hi in chunks]
        return _line_current(i_chunks, p_chunks, cfg), p_total
    if len(p_chunks) > 1:
        p_total = _add(p_chunks)     # whole-number counts: exact in any order
    # The barriers give the counts, each plane's pass over its blocks and
    # the SA epilogue fusions of their own.  Fused together, both planes'
    # block currents are live at once, the count blocks are read twice and
    # the pass's scalar constants become full-size operands.
    p_total, p_chunks = jax.lax.optimization_barrier((p_total, p_chunks))
    with jax.named_scope("ir_drop"):
        dropped = ni.ir_dropped_currents(blocks, spec.ir_alpha)
        i_line = _line_current([_add(dropped[lo:hi]) for lo, hi in chunks],
                               p_chunks, cfg)
    return jax.lax.optimization_barrier(i_line), p_total


def _add(terms):
    """Left-to-right sum of a sequence of arrays."""
    acc = terms[0]
    for t in terms[1:]:
        acc = acc + t
    return acc


def _block_sum(x: jax.Array, lo: int, hi: int) -> jax.Array:
    """x[..., lo:hi, :] summed over the block axis, as adds of static
    slices: elementwise, so it fuses with its consumers."""
    return _add([x[..., b, :] for b in range(lo, hi)])


def _line_current(i_chunks, p_chunks, cfg: ni.NonidealConfig) -> jax.Array:
    """The bit-line current from its chunks' currents: with the
    nonlinearity each chunk (the whole line in single shot) is distorted by
    its own activated-LRS count before the chunks add up."""
    if cfg.nonlinearity:
        i_chunks = [ni.apply_nonlinearity(i, p)
                    for i, p in zip(i_chunks, p_chunks)]
    return _add(i_chunks)


def sample_chip_planes(key: jax.Array, g_pos: jax.Array, g_neg: jax.Array,
                       scheme: str, cfg: ni.NonidealConfig,
                       spec: MacroSpec = DEFAULT_MACRO, device=None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sample ONE chip instance: effective conductance planes + SA key.

    Programming a die is static — the device-variation masks are drawn once
    per chip, not per MVM.  Returns (ep, en, k_sa) where ep/en carry the
    per-cell variation and HRS leak, and k_sa seeds the (per-read) peripheral
    stochastic terms.  Key-split discipline matches the historical
    `crossbar_forward` exactly, so `crossbar_forward(key, ...)` ==
    `crossbar_apply(k_sa, ..., *sample_chip_planes(key, ...)[:2])`.

    `device` selects the `repro.device` backend the variation masks and HRS
    leak come from (None: analytic — the historical closed forms,
    bit-identical; pinned by tests/test_device.py).  Each mask consumes the
    same split key regardless of backend, so swapping backends never shifts
    any other draw in the key stream.
    """
    dev = ni._device_or_analytic(device)
    k_var_p, k_var_n, k_sa = jax.random.split(key, 3)
    ep, en = g_pos, g_neg
    if cfg.device_variation:
        ep = g_pos * dev.variation_mask(k_var_p, g_pos.shape, spec)
        if scheme == "binary":
            # ONE shared physical reference line: its per-cell variation is
            # common to every output channel (input-dependent common offset,
            # Sec. IV-B.1)
            en = g_neg * dev.variation_mask(k_var_n, (g_neg.shape[0], 1),
                                            spec)
        else:
            en = g_neg * dev.variation_mask(k_var_n, g_neg.shape, spec)
    leak = dev.hrs_leak_units(spec)
    if leak:
        ep = ep + (1.0 - g_pos) * leak
        en = en + (1.0 - g_neg) * leak
    return ep, en, k_sa


def crossbar_apply(k_sa: jax.Array, x_ext: jax.Array,
                   ep: jax.Array, en: jax.Array,
                   gp: jax.Array, gn: jax.Array, *,
                   cfg: ni.NonidealConfig = ni.NonidealConfig.none(),
                   spec: MacroSpec = DEFAULT_MACRO,
                   accumulation: str = "single_shot",
                   partial_rows: int = 256,
                   sa_extra_units: float = 0.0,
                   output: str = "binary", device=None) -> jax.Array:
    """Deterministic-given-key forward through ONE sampled chip.

    x_ext: [..., rows] word-line bits with always-on rows already prefixed;
    ep/en: effective conductances (variation/leak applied); gp/gn: binary LRS
    placement planes (ideal counts).  This is the function `repro.mc` vmaps
    over a leading chips axis — all chip identity lives in (k_sa, ep, en).

    output: "binary" — SA decisions; "diff" — raw analog difference (ideal
    readout, for calibration); "sensed_diff" — the difference the periphery
    reports, with per-macro SA offset and sensing-range failures applied
    (what a digital combiner of multi-macro layers receives).

    `device`: the `repro.device` backend for periphery statistics (SA
    offset sigma, IR-drop factors); variation is already baked into ep/en
    by `sample_chip_planes` — pass the SAME backend to both.
    """
    blk = spec.ir_block
    i_pos, p_pos = _accumulate(_block_reduce(x_ext, ep, blk),
                               _block_reduce(x_ext, gp, blk),
                               cfg, spec, accumulation, partial_rows, device)
    i_neg, p_neg = _accumulate(_block_reduce(x_ext, en, blk),
                               _block_reduce(x_ext, gn, blk),
                               cfg, spec, accumulation, partial_rows, device)
    if output == "diff":
        return i_pos - i_neg
    p_pair = p_pos + p_neg
    if output == "sensed_diff":
        return ni.sensed_diff(k_sa, i_pos, i_neg, p_pair, cfg, spec,
                              sa_extra_units, device)
    return ni.resolve_sa(k_sa, i_pos, i_neg, p_pair, cfg, spec,
                         sa_extra_units, device)


def crossbar_forward(key: jax.Array, x_bits: jax.Array, mapped: MappedLayer,
                     *, cfg: ni.NonidealConfig = ni.NonidealConfig.none(),
                     spec: MacroSpec = DEFAULT_MACRO,
                     accumulation: str = "single_shot",
                     partial_rows: int = 256,
                     sa_extra_units: float = 0.0,
                     output: str = "binary", device=None) -> jax.Array:
    """Full structural crossbar simulation (sample one chip, then run it).

    x_bits: [..., fan_in] in {0,1}; returns [..., n_out]:
      output="binary": SA decisions in {0,1}
      output="diff":   analog current difference (for calibration / heads)

    Layers wider than the macro are tiled over multiple macros by the caller
    (see `IRCLinear`): this function simulates ONE macro's rows and asserts
    the planes fit.  Population studies should use `repro.mc`, which samples
    the chip state once per die and amortizes this forward over a chips axis.
    `device` selects the `repro.device` backend for BOTH the chip sampling
    and the periphery (None: analytic, bit-identical to the legacy path).
    """
    assert mapped.rows <= spec.rows, (
        f"planes ({mapped.rows} rows) exceed the macro ({spec.rows}); tile first")
    ep, en, k_sa = sample_chip_planes(key, mapped.g_pos, mapped.g_neg,
                                      mapped.scheme, cfg, spec, device)
    x_ext = extend_inputs(x_bits.astype(jnp.float32), mapped)
    return crossbar_apply(k_sa, x_ext, ep, en, mapped.g_pos, mapped.g_neg,
                          cfg=cfg, spec=spec, accumulation=accumulation,
                          partial_rows=partial_rows,
                          sa_extra_units=sa_extra_units, output=output,
                          device=device)


# ------------------------------------------------------------------ QAT surrogate

def variation_noise_std(p: jax.Array, sigma: float) -> jax.Array:
    """First-order std of a p-cell accumulated current under per-cell
    log-normal variation: sqrt(p) * std(lognormal(0, sigma))."""
    s2 = sigma * sigma
    cell_var = (jnp.exp(s2) - 1.0) * jnp.exp(s2)
    return jnp.sqrt(jnp.maximum(p, 0.0) * cell_var)


def irc_linear_train(key: jax.Array, x: jax.Array, w_latent: jax.Array, *,
                     cfg: ni.NonidealConfig = ni.NonidealConfig.none(),
                     spec: MacroSpec = DEFAULT_MACRO,
                     scheme: str = "ternary",
                     binarize_input: bool = True,
                     sa_beta: float = 4.0,
                     output: str = "binary") -> jax.Array:
    """Differentiable QAT path: quantized matmul + reparametrized noise.

    Matches the structural sim to first order: the current-difference noise
    from device variation has std sqrt(p_pair)*std_cell and the SA offset has
    std 0.5*g(p_pair); both are added to the pre-activation with fresh
    samples per step (variation-aware training, paper Sec. V / ref [5]).
    """
    if binarize_input:
        x = binary_activation(x)
    if scheme == "ternary":
        w_q = ternary_quantize(w_latent)
    elif scheme == "binary":
        w_q = binary_quantize(w_latent)
    else:
        raise ValueError(scheme)
    pre = x @ w_q
    if cfg.any():
        k1, k2 = jax.random.split(key)
        # expected activated-LRS count on the differential pair
        lrs_frac = jnp.mean(jnp.abs(jax.lax.stop_gradient(w_q)))
        p_pair = jnp.sum(jax.lax.stop_gradient(x), axis=-1, keepdims=True) * lrs_frac
        std = 0.0
        if cfg.device_variation:
            std = std + variation_noise_std(p_pair, spec.sigma_lrs)
        if cfg.sa_variation:
            std = std + 0.5 * ni.sa_required_diff(p_pair, spec)
        if cfg.device_variation or cfg.sa_variation:
            pre = pre + std * jax.random.normal(k1, pre.shape, pre.dtype)
    if output == "diff":
        return pre
    return soft_sa_output(pre, beta=sa_beta)


# ------------------------------------------------------------------ layer module

@dataclasses.dataclass(frozen=True)
class IRCLinearConfig:
    """Static configuration of one IRCLinear layer: shape, weight scheme,
    accumulation mode, and output stage."""
    fan_in: int
    fan_out: int
    scheme: str = "ternary"             # "ternary" (proposed) | "binary" (baseline)
    bias_rows: int = 0                  # extra common-mode bias rows (<= 32)
    accumulation: str = "single_shot"   # "single_shot" | "partial_sum"
    partial_rows: int = 256
    use_bn: bool = False                # baseline in-memory BN (Fig. 13a)
    output: str = "binary"              # "binary" | "diff"


class IRCLinear:
    """A linear layer executable ideally, via QAT surrogate, or through the
    full crossbar simulation; fan-in wider than one macro is tiled over
    multiple macros whose analog differences combine digitally (per-tile
    nonideal effects still apply)."""

    def __init__(self, config: IRCLinearConfig, spec: MacroSpec = DEFAULT_MACRO):
        self.config = config
        self.spec = spec

    def init(self, key: jax.Array) -> dict:
        """Initialize float parameters: fan-in-scaled Gaussian weights, plus
        identity BN statistics when `use_bn` is set."""
        c = self.config
        k_w, k_bn = jax.random.split(key)
        scale = 1.0 / jnp.sqrt(jnp.asarray(c.fan_in, jnp.float32))
        params = {"w": jax.random.normal(k_w, (c.fan_in, c.fan_out),
                                         jnp.float32) * scale}
        if c.use_bn:
            params["bn"] = {
                "gamma": jnp.ones((c.fan_out,), jnp.float32),
                "beta": jnp.zeros((c.fan_out,), jnp.float32),
                "mean": jnp.zeros((c.fan_out,), jnp.float32),
                "var": jnp.ones((c.fan_out,), jnp.float32),
            }
        return params

    def quantized_weights(self, params: dict) -> jax.Array:
        """Deployed weights under the configured scheme: ternary {-1,0,+1}
        (proposed) or binary {-1,+1} (baseline), straight-through in train."""
        if self.config.scheme == "ternary":
            return ternary_quantize(params["w"])
        return binary_quantize(params["w"])

    def map_to_planes(self, params: dict):
        """Build per-tile MappedLayers (static per deployment)."""
        from repro.core import mapping as mp
        c, spec = self.config, self.spec
        w_q = jax.lax.stop_gradient(self.quantized_weights(params))
        if c.scheme == "ternary":
            full = mp.ternary_planes(w_q, bias_rows=c.bias_rows)
        else:
            bn_units = None
            if c.use_bn:
                bn = params["bn"]
                bn_units = mp.fold_bn_to_bias_units(bn["gamma"], bn["beta"],
                                                    bn["mean"], bn["var"])
            full = mp.binary_planes(w_q, bn_bias_units=bn_units, spec=spec)
        lead = full.rows - full.fan_in   # always-on bias / BN rows (tile 0 only)
        tiles = []
        for lo in range(0, full.rows, spec.rows):
            hi = min(lo + spec.rows, full.rows)
            tile_lead = max(0, lead - lo) if lo < lead else 0
            tiles.append(MappedLayer(
                g_pos=full.g_pos[lo:hi], g_neg=full.g_neg[lo:hi],
                bias_rows=tile_lead, scheme=full.scheme,
                fan_in=(hi - lo) - tile_lead))
        return tiles

    def apply(self, params: dict, x: jax.Array, *, key: jax.Array,
              mode: str = "train",
              cfg: ni.NonidealConfig = ni.NonidealConfig.none(),
              sa_extra_units: float = 0.0) -> jax.Array:
        """Run the layer: `mode="train"` uses the differentiable QAT
        surrogate; `mode="eval"` runs the tiled structural crossbar sim."""
        c, spec = self.config, self.spec
        if mode == "train":
            return irc_linear_train(key, x, params["w"], cfg=cfg, spec=spec,
                                    scheme=c.scheme, output=c.output)
        # evaluation: full structural sim, tiled over macros.  Multi-tile
        # layers combine PER-TILE SENSED differences digitally: each macro's
        # SA front-end applies its own offset and sensing-range failures
        # before the combine ("diff" output stays the ideal analog readout
        # for calibration/heads).
        x_bits = jnp.where(x > 0, 1.0, 0.0).astype(jnp.float32)
        tiles = self.map_to_planes(params)
        multi = len(tiles) > 1
        tile_out = ("diff" if c.output == "diff"
                    else ("sensed_diff" if multi else "binary"))
        diffs = []
        offset = 0
        for t, tile in enumerate(tiles):
            k_t = jax.random.fold_in(key, t)
            lead = tile.rows - tile.fan_in
            x_t = x_bits[..., offset:offset + tile.rows - lead]
            offset += tile.rows - lead
            diffs.append(crossbar_forward(
                k_t, x_t, tile, cfg=cfg, spec=spec,
                accumulation=c.accumulation, partial_rows=c.partial_rows,
                sa_extra_units=sa_extra_units, output=tile_out))
        if not multi:
            return diffs[0]
        total = sum(diffs)
        if c.output == "diff":
            return total
        return (total > 0).astype(jnp.float32)


def ideal_ternary_matmul(x_bits: jax.Array, w_t: jax.Array) -> jax.Array:
    """Ideal digital reference: {0,1} inputs x ternary weights."""
    return x_bits.astype(jnp.float32) @ w_t.astype(jnp.float32)
