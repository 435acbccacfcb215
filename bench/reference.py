"""Plain float32 forward of a dense GQA decoder (Qwen2 / Mistral family),
and the reading that decides `correct` for a served model.

The model, as published: token embedding; per layer
    x += W_o . attn(RoPE(W_q h + b_q), RoPE(W_k h + b_k), W_v h + b_v),
         h = RMSNorm(x) * g1
    x += W_d (silu(W_g h) * (W_u h)),  h = RMSNorm(x) * g2
then RMSNorm(x) * g and the untied output head.  RoPE rotates the two
halves of each head (the `rotate_half` form of the published code);
attention is causal softmax(q k / sqrt(hd)), with query head j reading
key/value head j // (Hq / Hkv).

Departures from the published model, all shared with the served one:
random weights from `weights.py` (QKV biases and norm gains random too,
so both are exercised); no sliding window (the published configs do not
use one).  Everything here is float32 with every matrix product at
`Precision.HIGHEST`; no kernel, cache or batching.  Nothing of the program
is imported: weights are regenerated layer by layer from the seed.

`control=True` also computes the same forward with every linear layer in
fp8 (e4m3, operands scaled per output channel and per token, products
accumulated in float32): the next precision below the served bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import weights as W

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
PAD_TO = 256          # sequences are padded to a multiple of this


def _q8(a, axis):
    """Round ``a`` to fp8 with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                    1e-30) / F8_MAX
    return (a / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, x, w, fp8: bool, w_in_axes):
    """einsum of activation ``x`` (features last) and weight ``w``."""
    if fp8:
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: (S, H, hd); rotate halves by position."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = jnp.exp(-np.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, w, m, fp8: bool):
    """One decoder layer over a whole sequence x: (S, D) float32."""
    eps, hd = m["rms_norm_eps"], m["head_dim"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    S = x.shape[0]
    h = rms_norm(x, w["ln1"], eps)
    q = _mm("sd,dhk->shk", h, w["wq"], fp8, 0)
    k = _mm("sd,dhk->shk", h, w["wk"], fp8, 0)
    v = _mm("sd,dhk->shk", h, w["wv"], fp8, 0)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    q = q.reshape(S, hkv, hq // hkv, hd)
    s = jnp.einsum("sugk,tuk->ugst", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ugst,tuk->sugk", p, v, precision=HI).reshape(S, hq, hd)
    x = x + _mm("shk,hkd->sd", o, w["wo"], fp8, (0, 1))
    h = rms_norm(x, w["ln2"], eps)
    a = jax.nn.silu(_mm("sd,df->sf", h, w["wg"], fp8, 0)) \
        * _mm("sd,df->sf", h, w["wu"], fp8, 0)
    return x + _mm("sf,fd->sd", a, w["wd"], fp8, 0)


def _readings(x, o, nxt, fp8_x, *, m):
    """Gaps at every position of one padded sequence.

    ``nxt[s]`` is the token served after position s.  ``gap`` is the
    float32 reference's best logit minus that token's logit.  With
    ``fp8_x`` (the control's residual stream) also ``control_gap``: the
    same for the token the fp8 forward puts first."""
    eps = m["rms_norm_eps"]

    def logits(xx, fp8):
        return _mm("sd,dv->sv", rms_norm(xx, o["final_norm"], eps),
                   o["head"], fp8, 0)

    ref = logits(x, False)
    best = ref.max(-1)
    pick = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
    out = {"gap": best - pick(nxt)}
    if fp8_x is not None:
        out["control_gap"] = best - pick(jnp.argmax(logits(fp8_x, True), -1))
    return out


def _shardings(mesh):
    """Megatron-style split of the reference's own weights over the mesh."""
    col, row = P(None, "x", None), P("x", None, None)
    layer = {"ln1": P(), "ln2": P(), "wq": col, "wk": col, "wv": col,
             "wo": row, "bq": P("x", None), "bk": P("x", None),
             "bv": P("x", None), "wg": P(None, "x"), "wu": P(None, "x"),
             "wd": P("x", None)}
    outer = {"tok": P("x", None), "head": P(None, "x"), "final_norm": P()}
    ns = lambda t: {k: NamedSharding(mesh, s) for k, s in t.items()}
    return ns(layer), ns(outer)


def readings(seed: int, m: dict, seqs, *, control: bool = False,
             devices=None):
    """For each (tokens, n_prompt, served) in ``seqs``: the gap of every
    served token under the float32 reference (and, with ``control``, the
    gap of the fp8 forward's first choice).  ``tokens`` is the prompt plus
    every served token but the last; position n_prompt-1+i predicts
    served[i].  Runs layer by layer: one layer's weights at a time, split
    over ``devices`` (all devices when None)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    mesh = Mesh(np.asarray(devices), ("x",))
    lsh, osh = _shardings(mesh)
    rep = NamedSharding(mesh, P())
    key = W.base_key(seed)
    S = max(len(t) for t, _, _ in seqs)
    S = -(-S // PAD_TO) * PAD_TO
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        outer = jax.jit(lambda k: f32(W.outer_weights(k, m)),
                        out_shardings=osh)(key)
        layer_fn = jax.jit(
            lambda k, i: f32(W.layer_weights(k, i, m)),
            out_shardings={k: lsh[k] for k in _layer_keys(m)})
        blk = jax.jit(functools.partial(block, m=m, fp8=False),
                      out_shardings=rep)
        blk8 = jax.jit(functools.partial(block, m=m, fp8=True),
                       out_shardings=rep)
        ids = [jnp.asarray(np.pad(np.asarray(t, np.int32), (0, S - len(t))))
               for t, _, _ in seqs]
        embed = jax.jit(lambda tok, i, fp8: (_q8(tok, -1) if fp8 else tok)[i],
                        out_shardings=rep, static_argnums=2)
        xs = [embed(outer["tok"], i, False) for i in ids]
        x8 = [embed(outer["tok"], i, True) for i in ids] if control else None
        for i in range(m["num_hidden_layers"]):
            w = layer_fn(key, jnp.int32(i))
            xs = [blk(x, w) for x in xs]
            if control:
                x8 = [blk8(x, w) for x in x8]
            del w
        read = jax.jit(functools.partial(_readings, m=m))
        out = []
        for j, (t, n_prompt, served) in enumerate(seqs):
            lo, n = int(n_prompt) - 1, len(served)
            nxt = np.zeros(S, np.int32)
            nxt[lo:lo + n] = served
            r = read(xs[j], outer, jnp.asarray(nxt),
                     x8[j] if control else None)
            out.append({k: np.asarray(v)[lo:lo + n] for k, v in r.items()})
    return out


def _layer_keys(m):
    keys = ["ln1", "ln2", "wq", "wk", "wv", "wo", "wg", "wu", "wd"]
    return keys + (["bq", "bk", "bv"] if m["qkv_bias"] else [])
