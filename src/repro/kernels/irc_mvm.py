"""Pallas TPU kernel: fused single-shot IRC crossbar MVM + nonideal epilogue.

This is the compute hot spot of the structural simulation (paper Secs. III-IV):
for each (batch, output-channel) tile it computes, entirely in VMEM,

  1. per-32-row-sub-block partial currents for both conductance planes
     (the IR-drop block model needs them individually) — one MXU dot per
     block on a static 32-lane slice of the word-line tile;
  2. activated-LRS counts per plane — two MXU dots;
  3. the fused epilogue: IR-drop weighting (the min-matrix contraction of
     `repro.core.nonideal.ir_drop_factors`, one small MXU dot per tile
     row), the paper's piecewise-quartic accumulation nonlinearity,
     differential SA comparison with offset noise and limited-sensing-range
     fallback.

Every dot runs at `Precision.HIGHEST` (full f32): the planes carry
variation-scaled conductances, and bf16 rounding is the same order as the
variation being simulated.

A naive jnp composition round-trips [B, n_blocks, N] block currents and the
count/current tensors through HBM ~10 times; the kernel keeps everything in
VMEM scratch across the R-dimension grid walk and writes only the [B, N]
binary output.

Tiling: grid = (B/bm, N/bn, R/bk) with the R walk innermost ("arbitrary"
semantics, accumulation in scratch).  Defaults bm=8 (sublane), bn=128
(lane), bk=256 (8 IR blocks per step) — sweepable.  VMEM scratch is two
(R/32, bm, bn) block-current buffers plus four (bm, bn) tiles: at
most 0.72 MB at the detector's R = 572 for every autotune candidate, compiled
for v5e by tests/test_tpu_compile.py.

Stochastic terms (SA offset noise, unresolvable-comparison fallback bits)
are pre-sampled inputs, so the kernel is deterministic and exactly testable
against `ref.irc_mvm_ref` (interpret=True on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nonideal import ir_drop_factors
from repro.kernels.ref import IrcEpilogueParams, _NL_LO, _NL_HI


def _nl_ratio_inline(p: jax.Array) -> jax.Array:
    p_raw = p
    p = jnp.clip(p_raw, 0.0, 320.0)
    def horner(c):
        acc = jnp.full_like(p, c[0])
        for x in c[1:]:
            acc = acc * p + x
        return acc
    ratio = jnp.where(p <= 140.0, horner(_NL_LO), horner(_NL_HI))
    return jnp.where(p_raw < 0.5, 1.0, ratio)


def _accum_step(x, ep, en, gp, gn, blocks_p, blocks_n, p_pos, p_neg,
                k, nbk, blk):
    """One R-walk step: full-tile count dots + per-IR-block partial-current
    dots, accumulated into the VMEM scratch (shared by both kernels).

    Each IR block is a static 32-lane slice of the word-line tile against
    the matching (sublane-aligned) 32 plane rows: Mosaic lowers these
    slices, where splitting the lane axis by reshape is refused.  Every dot
    runs at `HIGHEST`, i.e. in full f32 on the MXU."""
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    p_pos[...] += dot(x, gp)
    p_neg[...] += dot(x, gn)
    for j in range(nbk):
        cols = slice(j * blk, (j + 1) * blk)
        blocks_p[k * nbk + j] = dot(x[:, cols], ep[cols])    # (bm, bn)
        blocks_n[k * nbk + j] = dot(x[:, cols], en[cols])


def _line_currents(blocks, line, params: IrcEpilogueParams) -> None:
    """Bit-line currents of one (bm, bn) tile from its per-block currents
    `blocks` (NBT, bm, bn), written into `line` (bm, bn).  With IR drop each
    tile row's [NBT, bn] slab is weighted by `ir_drop_factors` — the
    min-matrix contraction that defines the drop (the structural simulation
    computes the same sums in one pass, `ni.ir_dropped_currents`)."""
    if not params.apply_ir:
        line[...] = jnp.sum(blocks[...], axis=0)
        return
    for m in range(line.shape[0]):
        slab = blocks[:, m, :]                                # (NBT, bn)
        slab = slab * ir_drop_factors(slab, params.ir_alpha, axis=-2)
        line[pl.ds(m, 1), :] = jnp.sum(slab, axis=0, keepdims=True)


def _epilogue_tile(i_pos, i_neg, pp, pn, eps, rnd,
                   params: IrcEpilogueParams) -> jax.Array:
    """Fused VPU epilogue on one (bm, bn) tile of IR-dropped bit-line
    currents: accumulation nonlinearity, SA comparison + sensing-range
    fallback."""
    if params.apply_nonlinearity:
        i_pos = i_pos * _nl_ratio_inline(pp)
        i_neg = i_neg * _nl_ratio_inline(pn)
    diff = i_pos - i_neg
    if params.output == "diff":
        return diff
    if params.apply_sa:
        p_pair = pp + pn
        sigma = 0.5 * (params.sa_c0 + params.sa_c1 * p_pair
                       + params.sa_c2 * p_pair * p_pair + params.sa_extra)
        diff = diff + sigma * eps
    out = (diff > 0).astype(jnp.float32)
    if params.apply_range:
        fail = jnp.logical_or(
            jnp.minimum(i_pos, i_neg) < params.sense_low,
            jnp.maximum(i_pos, i_neg) > params.sense_high)
        out = jnp.where(fail, rnd, out)
    return out


def _finish(blocks_p, blocks_n, line_p, line_n, p_pos, p_neg, eps, rnd,
            params: IrcEpilogueParams) -> jax.Array:
    """Last R step of a tile: IR-dropped line currents, then the epilogue."""
    _line_currents(blocks_p, line_p, params)
    _line_currents(blocks_n, line_n, params)
    return _epilogue_tile(line_p[...], line_n[...], p_pos[...], p_neg[...],
                          eps, rnd, params)


def _irc_mvm_kernel(x_ref, ep_ref, en_ref, gp_ref, gn_ref, eps_ref, rnd_ref,
                    out_ref, blocks_p, blocks_n, line_p, line_n, p_pos, p_neg,
                    *, params: IrcEpilogueParams, nk: int, bk: int):
    k = pl.program_id(2)
    blk = params.ir_block
    nbk = bk // blk                      # IR blocks contributed this step

    @pl.when(k == 0)
    def _init():
        # every IR block slot is written by exactly one R step; only the
        # count accumulators need zeroing
        p_pos[...] = jnp.zeros_like(p_pos)
        p_neg[...] = jnp.zeros_like(p_neg)

    _accum_step(x_ref[...].astype(jnp.float32),
                ep_ref[...].astype(jnp.float32),
                en_ref[...].astype(jnp.float32),
                gp_ref[...].astype(jnp.float32),
                gn_ref[...].astype(jnp.float32),
                blocks_p, blocks_n, p_pos, p_neg, k, nbk, blk)

    @pl.when(k == nk - 1)
    def _epilogue():
        out_ref[...] = _finish(blocks_p, blocks_n, line_p, line_n,
                               p_pos, p_neg, eps_ref[...], rnd_ref[...],
                               params)


def _irc_mvm_chips_kernel(x_ref, ep_ref, en_ref, gp_ref, gn_ref, eps_ref,
                          rnd_ref, out_ref, blocks_p, blocks_n, line_p,
                          line_n, p_pos, p_neg, *, params: IrcEpilogueParams,
                          nk: int, bk: int, shared_counts: bool,
                          per_chip_x: bool):
    """Chip-batched variant: grid (chips, B/bm, N/bn, R/bk); the plane /
    periphery refs carry a leading length-1 chip block.  The word-line tile
    is SHARED by every chip by default (one ensemble evaluates one input
    batch), so the extra grid dimension reuses the x block across the chip
    walk; with `per_chip_x` the word-line tile carries its own length-1 chip
    block instead — how network-level MC feeds chip-diverged activations
    from one IRC layer into the next.  With `shared_counts` the LRS
    placement planes are chip-independent too and arrive as plain 2-D tiles
    (one HBM copy serves every chip)."""
    k = pl.program_id(3)
    blk = params.ir_block
    nbk = bk // blk

    @pl.when(k == 0)
    def _init():
        # every IR block slot is written by exactly one R step; only the
        # count accumulators need zeroing
        p_pos[...] = jnp.zeros_like(p_pos)
        p_neg[...] = jnp.zeros_like(p_neg)

    gp = gp_ref[...] if shared_counts else gp_ref[0]
    gn = gn_ref[...] if shared_counts else gn_ref[0]
    x = x_ref[0] if per_chip_x else x_ref[...]
    _accum_step(x.astype(jnp.float32),
                ep_ref[0].astype(jnp.float32),
                en_ref[0].astype(jnp.float32),
                gp.astype(jnp.float32),
                gn.astype(jnp.float32),
                blocks_p, blocks_n, p_pos, p_neg, k, nbk, blk)

    @pl.when(k == nk - 1)
    def _epilogue():
        out_ref[0] = _finish(blocks_p, blocks_n, line_p, line_n,
                             p_pos, p_neg, eps_ref[0], rnd_ref[0], params)


def irc_mvm_pallas(x: jax.Array, ep: jax.Array, en: jax.Array,
                   gp: jax.Array, gn: jax.Array,
                   eps_sa: jax.Array, rnd_bits: jax.Array,
                   params: IrcEpilogueParams,
                   *, bm: int = 8, bn: int = 128, bk: int = 256,
                   interpret: bool = False) -> jax.Array:
    """Raw pallas_call wrapper; shapes must already be tile-aligned
    (B % bm == N % bn == R % bk == 0, bk % ir_block == 0).  Use
    `repro.kernels.ops.irc_mvm` for the padded/jit public entry point."""
    B, R = x.shape
    N = ep.shape[1]
    assert R % bk == 0 and bk % params.ir_block == 0, (R, bk, params.ir_block)
    assert B % bm == 0 and N % bn == 0, (B, bm, N, bn)
    nk = R // bk
    nbt = R // params.ir_block

    grid = (B // bm, N // bn, nk)
    kernel = functools.partial(_irc_mvm_kernel, params=params, nk=nk, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # ep
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # en
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # gp
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # gn
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),   # eps_sa
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),   # rnd_bits
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nbt, bm, bn), jnp.float32),   # blocks_p
            pltpu.VMEM((nbt, bm, bn), jnp.float32),   # blocks_n
            pltpu.VMEM((bm, bn), jnp.float32),        # line_p
            pltpu.VMEM((bm, bn), jnp.float32),        # line_n
            pltpu.VMEM((bm, bn), jnp.float32),        # p_pos
            pltpu.VMEM((bm, bn), jnp.float32),        # p_neg
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, ep, en, gp, gn, eps_sa, rnd_bits)


def irc_mvm_chips_pallas(x: jax.Array, ep: jax.Array, en: jax.Array,
                         gp: jax.Array, gn: jax.Array,
                         eps_sa: jax.Array, rnd_bits: jax.Array,
                         params: IrcEpilogueParams,
                         *, bm: int = 8, bn: int = 128, bk: int = 256,
                         interpret: bool = False) -> jax.Array:
    """Chip-batched raw wrapper: one launch services a whole chip ensemble.

    x [B, R] is shared — or [C, B, R] with a per-chip word-line stream
    (chip-diverged activations downstream of the first IRC layer); ep/en
    [C, R, N] and eps/rnd [C, B, N] carry the chips axis; gp/gn are either
    [C, R, N] (per-chip placement, e.g. after per-die bias calibration) or
    [R, N] (shared placement — one HBM copy serves every chip); output is
    [C, B, N].  The chips grid dimension is outermost and fully parallel —
    on TPU the C x (B/bm) x (N/bn) tiles schedule like one big MVM instead
    of C kernel launches.  Shapes must be tile-aligned (use
    `repro.kernels.ops.irc_mvm_chips` for the padded entry point).
    """
    per_chip_x = x.ndim == 3
    B, R = x.shape[-2:]
    C, _, N = ep.shape
    shared_counts = gp.ndim == 2
    assert R % bk == 0 and bk % params.ir_block == 0, (R, bk, params.ir_block)
    assert B % bm == 0 and N % bn == 0, (B, bm, N, bn)
    nk = R // bk
    nbt = R // params.ir_block

    grid = (C, B // bm, N // bn, nk)
    kernel = functools.partial(_irc_mvm_chips_kernel, params=params,
                               nk=nk, bk=bk, shared_counts=shared_counts,
                               per_chip_x=per_chip_x)
    plane = pl.BlockSpec((1, bk, bn), lambda c, i, j, k: (c, k, j))
    count = (pl.BlockSpec((bk, bn), lambda c, i, j, k: (k, j))
             if shared_counts else plane)
    peri = pl.BlockSpec((1, bm, bn), lambda c, i, j, k: (c, i, j))
    x_spec = (pl.BlockSpec((1, bm, bk), lambda c, i, j, k: (c, i, k))
              if per_chip_x
              else pl.BlockSpec((bm, bk), lambda c, i, j, k: (i, k)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            x_spec,                                              # x
            plane, plane, count, count,                          # ep en gp gn
            peri, peri,                                          # eps_sa, rnd
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda c, i, j, k: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, B, N), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nbt, bm, bn), jnp.float32),   # blocks_p
            pltpu.VMEM((nbt, bm, bn), jnp.float32),   # blocks_n
            pltpu.VMEM((bm, bn), jnp.float32),        # line_p
            pltpu.VMEM((bm, bn), jnp.float32),        # line_n
            pltpu.VMEM((bm, bn), jnp.float32),        # p_pos
            pltpu.VMEM((bm, bn), jnp.float32),        # p_neg
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, ep, en, gp, gn, eps_sa, rnd_bits)
