"""Seeded random weights for a dense GQA decoder, made on the device.

One generator serves both sides of the comparison that decides `correct`:

* the system under test gets every layer at once, stacked on a leading
  layer axis, in its own slot layout (`program_params`), created by one
  jitted call straight into the shardings its steps read them in;
* the plain reference regenerates one layer at a time
  (`layer_weights`), in the published layout, after the program's state
  has been freed.

Both read the same values because every leaf is drawn from a key derived
from `(seed, layer, leaf)` alone.  Nothing here imports the program: its
head-slot maps (`q_map`, `kv_map`: slot -> head, -1 for a dead slot) are
passed in as plain tuples.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# std of the random QKV biases and of the norm gains around 1: large
# enough that dropping either moves the logits well past rounding
BIAS_STD = 0.5
NORM_STD = 0.1


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed up to 64 bits (PRNGKey takes 32)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_weights(key: jax.Array, layer, m: dict) -> dict:
    """Layer ``layer`` (an int or a traced int32) in the published layout:
    wq (D, Hq, hd), wk/wv (D, Hkv, hd), wo (Hq, hd, D), biases (H, hd),
    wg/wu (D, F), wd (F, D), norm gains (D,)."""
    d, f, hd = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    dt = DTYPES[m["dtype"]]
    ks = jax.random.split(jax.random.fold_in(key, layer + 1), 12)
    w = {
        "ln1": 1.0 + _normal(ks[0], (d,), NORM_STD, jnp.float32),
        "ln2": 1.0 + _normal(ks[1], (d,), NORM_STD, jnp.float32),
        "wq": _normal(ks[2], (d, hq, hd), 1 / math.sqrt(d), dt),
        "wk": _normal(ks[3], (d, hkv, hd), 1 / math.sqrt(d), dt),
        "wv": _normal(ks[4], (d, hkv, hd), 1 / math.sqrt(d), dt),
        "wo": _normal(ks[5], (hq, hd, d), 1 / math.sqrt(hq * hd), dt),
        "wg": _normal(ks[6], (d, f), 1 / math.sqrt(d), dt),
        "wu": _normal(ks[7], (d, f), 1 / math.sqrt(d), dt),
        "wd": _normal(ks[8], (f, d), 1 / math.sqrt(f), dt),
    }
    w["ln1"] = w["ln1"].astype(dt)
    w["ln2"] = w["ln2"].astype(dt)
    if m["qkv_bias"]:
        w["bq"] = _normal(ks[9], (hq, hd), BIAS_STD, dt)
        w["bk"] = _normal(ks[10], (hkv, hd), BIAS_STD, dt)
        w["bv"] = _normal(ks[11], (hkv, hd), BIAS_STD, dt)
    return w


def outer_weights(key: jax.Array, m: dict) -> dict:
    """Token embedding (V, D), output head (D, V), final norm gain (D,)."""
    d, v = m["hidden_size"], m["vocab_size"]
    dt = DTYPES[m["dtype"]]
    ke, kh, kn = jax.random.split(jax.random.fold_in(key, 0), 3)
    return {"tok": _normal(ke, (v, d), 1 / math.sqrt(d), dt),
            "head": _normal(kh, (d, v), 1 / math.sqrt(d), dt),
            "final_norm": (1.0 + _normal(kn, (d,), NORM_STD,
                                         jnp.float32)).astype(dt)}


def _slots(w, head_map, axis):
    """Heads -> the program's slots along ``axis`` (dead slots zero)."""
    hm = np.asarray(head_map)
    idx = np.maximum(hm, 0)
    out = jnp.take(w, jnp.asarray(idx), axis=axis)
    live = (hm >= 0).reshape([-1 if a == axis else 1
                              for a in range(out.ndim)])
    return jnp.where(jnp.asarray(live), out, jnp.zeros((), out.dtype))


def program_layer(w: dict, q_map, kv_map) -> dict:
    """One layer in the program's tree and slot layout."""
    attn = {"wq": _slots(w["wq"], q_map, 1), "wk": _slots(w["wk"], kv_map, 1),
            "wv": _slots(w["wv"], kv_map, 1), "wo": _slots(w["wo"], q_map, 0)}
    if "bq" in w:
        attn.update(bq=_slots(w["bq"], q_map, 0),
                    bk=_slots(w["bk"], kv_map, 0),
                    bv=_slots(w["bv"], kv_map, 0))
    return {"ln1": {"w": w["ln1"]}, "attn": attn, "ln2": {"w": w["ln2"]},
            "mlp": {"wg": w["wg"], "wu": w["wu"], "wd": w["wd"]}}


def program_params(seed: int, m: dict, q_map, kv_map, vocab_pad: int,
                   out_shardings=None):
    """Every weight of the model in the program's layout, made by one
    jitted call on the device (layers written one at a time by
    ``lax.map``, so no second copy of the stack is held)."""
    n_layers = m["num_hidden_layers"]

    def make(key):
        blocks = jax.lax.map(
            lambda i: program_layer(layer_weights(key, i, m), q_map, kv_map),
            jnp.arange(n_layers, dtype=jnp.int32))
        o = outer_weights(key, m)
        pad = vocab_pad - m["vocab_size"]
        embed = {"tok": jnp.pad(o["tok"], ((0, pad), (0, 0))),
                 "head": jnp.pad(o["head"], ((0, 0), (0, pad)))}
        return {"embed": embed, "blocks": blocks,
                "final_norm": {"w": o["final_norm"]}}

    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(make, **kw)(base_key(seed))
