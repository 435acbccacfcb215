"""Pure-jnp oracle for decode attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1.0e30


def decode_attention_ref(q, k, v, positions, *, window: int = 0):
    """q: (B, Hq, hd); k/v: (B, S, Hkv, hd); positions: (B,)."""
    B, Hq, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bugh,bsuh->bugs", qg, k.astype(jnp.float32)) \
        * (hd ** -0.5)
    kp = jnp.arange(S)[None, :]
    mask = kp <= positions[:, None]
    if window > 0:
        mask &= kp > positions[:, None] - window
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bugs,bsuh->bugh", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, hd).astype(q.dtype)


__all__ = ["decode_attention_ref"]
