"""Pallas TPU kernel: single-token GQA decode attention (flash-decode).

One new query token attends over a long KV cache.  The cache is streamed
through VMEM in ``block_s``-sized chunks with an online-softmax running
(max, sum, acc) carried in VMEM scratch across the sequential s-grid axis;
per-sequence valid lengths are scalar-prefetched so padding slots beyond
the cache fill never contribute.

Grid: (batch, kv_heads, S/block_s) — batch and head axes are parallel, the
sequence axis is the sequential accumulation axis.

VMEM per step (f32): q (g, D) + k/v (block_s, D) x2 + acc (g, D): with
g = 16 query heads/group, D = 128, block_s = 512 this is ~0.6 MiB, double
buffered — the DMA of chunk s+1 overlaps the matmuls of chunk s.

Supports the gemma2 logit soft-cap (scores = cap * tanh(scores / cap)).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import interpret_mode

NEG_BIG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, softcap: float, block_s: int):
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                      # [g, D]
    k = k_ref[0, 0]                      # [block_s, D]
    v = v_ref[0, 0]                      # [block_s, D]
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    pos = s * block_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    mask = pos < len_ref[b]
    scores = jnp.where(mask, scores, NEG_BIG)

    m_prev = m_ref[...]                  # [g, 1]
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)      # [g, block_s]
    alpha = jnp.exp(m_prev - m_new)                        # [g, 1]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _done():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("scale", "softcap", "block_s", "interpret"))
def decode_attention_grouped(q: jax.Array, k: jax.Array, v: jax.Array,
                             lengths: jax.Array, *, scale: float,
                             softcap: float = 0.0, block_s: int = 512,
                             interpret: Optional[bool] = None) -> jax.Array:
    """q [B, Hkv, g, D]; k, v [B, Hkv, S, D]; lengths [B] -> out [B, Hkv, g, D]."""
    B, Hkv, g, D = q.shape
    S = k.shape[2]
    assert S % block_s == 0, (S, block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, S // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, g, D), lambda b, h, s, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, s, lens: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, s, lens: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, D), lambda b, h, s, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, scale=scale, softcap=softcap,
                             block_s=block_s)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
    )(lengths, q, k, v)
