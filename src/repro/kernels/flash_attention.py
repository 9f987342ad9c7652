"""Pallas TPU kernel: causal flash attention (online softmax).

Why it exists here: the 32k-context prefill cells are MEMORY-bound on
materialized [.., Sq, Sk] score/prob tensors (measured 17 GB per layer per
device on chameleon-34b prefill_32k even with the KV sequence sharded
16-way).  Flash attention keeps the score block in VMEM and streams KV
blocks with a running (max, denominator) — HBM traffic drops from
O(Sq*Sk) to O(Sq*hd + Sk*hd).

Grid: (batch*heads, Sq/bq, Sk/bk), KV walk innermost with VMEM scratch for
the accumulator and the online-softmax stats.  Causality skips fully-masked
KV blocks via pl.when.  Validated against ref.py's oracle in interpret
mode; the multi-pod dry-run keeps the XLA attention (Mosaic kernels cannot
compile on the CPU dry-run backend) — §Perf carries the analytic traffic
correction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s,
                  *, scale: float, bq: int, bk: int, nk: int, causal: bool):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def body():
        q = q_ref[0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)            # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_s[...]                           # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    if causal:
        # skip KV blocks strictly in the future of this whole q block
        pl.when(kb * bk <= qb * bq + bq - 1)(body)
    else:
        body()

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-20)
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True, bq: int = 512,
                           bk: int = 512, interpret: bool = False
                           ) -> jax.Array:
    """q [H, Sq, hd], k/v [H, Sk, hd] -> [H, Sq, hd].
    (vmap over batch; H = flattened heads.)  Sq % bq == Sk % bk == 0."""
    H, Sq, hd = q.shape
    Sk = k.shape[1]
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nk = Sk // bk
    scale = hd ** -0.5
    kernel = functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk,
                               nk=nk, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(H, Sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),      # output accumulator
            pltpu.VMEM((bq, 1), jnp.float32),       # running max
            pltpu.VMEM((bq, 1), jnp.float32),       # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
