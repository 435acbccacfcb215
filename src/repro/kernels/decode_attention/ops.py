"""Public wrapper: batch/sequence padding for the decode-attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import decode_attention_call


def decode_attention(q, k, v, positions, *, window: int = 0,
                     block_b: int = 8, block_k: int = 256,
                     interpret=False):
    """q: (B, Hq, hd); k/v: (B, S, Hkv, hd); positions: (B,) -> (B, Hq, hd).
    """
    B, Hq, hd = q.shape
    S = k.shape[1]
    pad_b = (-B) % block_b
    pad_s = (-S) % block_k
    if pad_b:
        q = jnp.pad(q, ((0, pad_b), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, pad_b), (0, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad_b), (0, 0), (0, 0), (0, 0)))
        positions = jnp.pad(positions, (0, pad_b))
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    out = decode_attention_call(q, k, v, positions, window=window,
                                q_per_kv=Hq // k.shape[2],
                                block_b=block_b, block_k=block_k,
                                interpret=interpret)
    return out[:B]


__all__ = ["decode_attention"]
