"""Pallas TPU kernel: dense ternary matmul (the ideal digital fast path).

Ternary weights are stored as int8 {-1,0,+1} (4x smaller than f32 in HBM —
the layer is memory-bound at inference batch sizes) and upcast to the MXU
input type inside VMEM.  Classic three-loop tiled matmul with an f32 VMEM
accumulator; the R walk is the innermost grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ternary_matmul_kernel(x_ref, w_ref, out_ref, acc, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc[...] += jax.lax.dot_general(
        x, w, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = acc[...]


def ternary_matmul_pallas(x: jax.Array, w_t: jax.Array,
                          *, bm: int = 128, bn: int = 128, bk: int = 512,
                          interpret: bool = False) -> jax.Array:
    """x [B,K] float, w_t [K,N] int8 {-1,0,1} -> f32 [B,N].
    Tile-aligned shapes required; see ops.ternary_matmul for padding."""
    B, K = x.shape
    N = w_t.shape[1]
    assert B % bm == 0 and K % bk == 0 and N % bn == 0, (B, K, N, bm, bk, bn)
    nk = K // bk
    kernel = functools.partial(_ternary_matmul_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_t)
