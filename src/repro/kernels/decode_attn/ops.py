"""User-facing decode attention: flat-head layout + cache padding."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn.kernel import decode_attention_grouped


def decode_attention(q, k_cache, v_cache, lengths, *, softcap: float = 0.0,
                     block_s: int = 512, interpret: Optional[bool] = None) -> jax.Array:
    """GQA decode attention.

    q:        [B, H, D]   one new token per sequence
    k_cache:  [B, S, Hkv, D]
    v_cache:  [B, S, Hkv, D]
    lengths:  [B] int32 valid prefix per sequence
    returns   [B, H, D]
    """
    B, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, Hkv, g, D)
    kt = jnp.swapaxes(k_cache, 1, 2)      # [B, Hkv, S, D]
    vt = jnp.swapaxes(v_cache, 1, 2)
    pad = (-S) % block_s
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = decode_attention_grouped(qg.astype(jnp.float32),
                                   kt.astype(jnp.float32),
                                   vt.astype(jnp.float32),
                                   lengths.astype(jnp.int32),
                                   scale=scale, softcap=softcap,
                                   block_s=block_s, interpret=interpret)
    return out.reshape(B, H, D)
