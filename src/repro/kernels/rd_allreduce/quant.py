"""Group-quantized pack/unpack for low-bit collectives (reference math).

The quantized all-reduce strategies (``core.hierarchical``, ``ar_quant=
int8|int4``) ship payloads as int8 arrays + per-group bf16 scales; int4
packs two values per byte (nibble layout) so the wire really carries half
the bytes — the HLO byte accountant (``launch.hlo_analysis``) has no s4
dtype, so anything narrower than a byte must be physically packed to count.

Layout contract (shared with the Pallas kernel in ``quant_kernel``):

- Groups run along the **last** dim only: ``x[..., k*group:(k+1)*group]``
  shares one bf16 scale.  Never across batch/sequence dims — that is what
  keeps serving slots independent (one request's magnitudes cannot poison
  another's scales) and makes the overlapped chunked matmul bitwise
  chunk-invariant whenever the chunk step is a multiple of ``group``.
- ``scale = max|group| / qmax`` (symmetric), clamped to 1e-30 so all-zero
  groups stay exact; a NaN/Inf in the group makes the *scale* non-finite,
  so dequantization poisons exactly that group and the serving stack's
  finite-logits quarantine (DESIGN.md §11) still fires.  No masking.
- int4 values live in [-7, 7] (we give up -8 for symmetry); packing pairs
  adjacent elements ``(2i, 2i+1)`` into one byte (low nibble first).
  Pairs may cross group boundaries — packing is independent of scaling.

All reference functions are plain jnp (traceable inside shard_map); the
collectives call these, while ``quant_kernel`` provides the fused Pallas
variant, checked bit-equal to them in ``tests/test_kernels.py``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

QMAX = {8: 127, 4: 7}
# Default/maximum group sizes: chosen so the wire factor clears the
# acceptance bars with bf16 payloads (see wire_factor):
#   int8 g=128: (1 + 2/128)/2   = 0.5078 -> 1.97x reduction
#   int4 g=64:  (0.5 + 2/64)/2  = 0.2656 -> 3.76x reduction
GROUP_CAP = {8: 128, 4: 64}
_EPS = 1e-30


def group_for(n_last: int, bits: int) -> int:
    """Largest power-of-2 divisor of ``n_last``, capped per ``bits``.

    Power-of-2 keeps groups aligned with the 2^k shard splits the
    hierarchical strategies perform along the same dim.
    """
    if n_last <= 0:
        return 1
    low = n_last & (-n_last)            # largest pow2 dividing n_last
    return min(low, GROUP_CAP[bits])


def wire_factor(bits: int, group: int, dtype_bytes: int = 2) -> float:
    """Quantized wire bytes per full-precision wire byte (payload+scales)."""
    return (bits / 8.0 + 2.0 / group) / dtype_bytes


def packed_width(n_last: int, bits: int) -> int:
    """Byte width of the packed payload for a trailing dim of ``n_last``."""
    if bits == 8:
        return n_last
    assert n_last % 2 == 0, n_last
    return n_last // 2


def quantize_pack(x: jax.Array, bits: int,
                  group: int) -> Tuple[jax.Array, jax.Array]:
    """(..., D) -> (packed int8 (..., Dp), scales bf16 (..., D/group)).

    Requires D % group == 0 and, for int4, D even (callers pad).
    Saturation-safe: values are clipped to [-qmax, qmax] after rounding.
    """
    qmax = QMAX[bits]
    D = x.shape[-1]
    assert D % group == 0, (D, group)
    g = x.astype(jnp.float32).reshape(x.shape[:-1] + (D // group, group))
    absmax = jnp.max(jnp.abs(g), axis=-1)
    scale = jnp.maximum(absmax / qmax, _EPS)     # NaN/Inf propagate
    q = jnp.clip(jnp.round(g / scale[..., None]), -qmax, qmax)
    q = q.astype(jnp.int32).reshape(x.shape[:-1] + (D,))
    if bits == 4:
        assert D % 2 == 0, D
        pairs = q.reshape(x.shape[:-1] + (D // 2, 2))
        v = (pairs[..., 0] & 0xF) | ((pairs[..., 1] & 0xF) << 4)
        q = jnp.where(v > 127, v - 256, v)       # reinterpret as int8 bits
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def unpack_dequant(packed: jax.Array, scales: jax.Array, bits: int,
                   group: int) -> jax.Array:
    """Inverse of :func:`quantize_pack`; returns f32 (..., D)."""
    if bits == 4:
        v = packed.astype(jnp.int32) & 0xFF
        lo = v & 0xF
        hi = (v >> 4) & 0xF
        lo = jnp.where(lo > 7, lo - 16, lo)
        hi = jnp.where(hi > 7, hi - 16, hi)
        q = jnp.stack([lo, hi], axis=-1).reshape(
            packed.shape[:-1] + (packed.shape[-1] * 2,))
    else:
        q = packed.astype(jnp.int32)
    D = q.shape[-1]
    assert D % group == 0, (D, group)
    g = q.reshape(q.shape[:-1] + (D // group, group)).astype(jnp.float32)
    out = g * scales.astype(jnp.float32)[..., None]
    return out.reshape(q.shape[:-1] + (D,))


__all__ = ["QMAX", "GROUP_CAP", "group_for", "wire_factor", "packed_width",
           "quantize_pack", "unpack_dequant"]
