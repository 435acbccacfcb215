"""Operations and bytes of a dense GQA decoder, from its shapes alone.

`m` is a configuration's model dict (published key names); `tp` the
number of chips that share each layer (heads, feed-forward columns and
vocabulary split `tp` ways, norms held whole on every chip).  FLOPs count
a multiply-add as two; bytes are what the step must move through HBM at
the least: every weight it uses once, the K/V it reads and writes.
Padding, dead slots, idle batch rows and recomputation are not counted,
so a share of a peak computed from these never exceeds what the chip did.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def _dims(m):
    return (m["hidden_size"], m["intermediate_size"], m["head_dim"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"], m["num_hidden_layers"])


def layer_matmul_params(m) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    d, f, hd, hq, hkv, _, _ = _dims(m)
    return d * hd * (2 * hq + 2 * hkv) + 3 * d * f


def param_count(m) -> int:
    """Every weight: embedding, head, layers (biases, norms), final norm."""
    d, _, hd, hq, hkv, v, n = _dims(m)
    per_layer = layer_matmul_params(m) + 2 * d
    if m["qkv_bias"]:
        per_layer += hd * (hq + 2 * hkv)
    embed = v * d * (1 if m.get("tie_word_embeddings") else 2)
    return embed + n * per_layer + d


def param_bytes_per_chip(m, tp: int) -> int:
    """Weight bytes one of ``tp`` chips holds: norms whole, all else 1/tp."""
    d, _, _, _, _, _, n = _dims(m)
    whole = (2 * n + 1) * d
    return BYTES[m["dtype"]] * ((param_count(m) - whole) // tp + whole)


def kv_bytes_per_token(m, tp: int) -> int:
    """K and V of one position over all layers, on one chip."""
    _, _, hd, _, hkv, _, n = _dims(m)
    return BYTES[m["dtype"]] * 2 * n * hkv * hd // tp


def attention_flops(m, keys: int) -> int:
    """q.k and p.v of one query position over ``keys`` positions, all
    layers (whole model)."""
    _, _, hd, hq, _, _, n = _dims(m)
    return 4 * n * hq * hd * keys


def token_flops(m, keys: int, logits: bool) -> int:
    """Model FLOPs of one token (whole model) that attends ``keys``
    positions; ``logits``: the output head is computed for it."""
    d, _, _, _, _, v, n = _dims(m)
    f = 2 * n * layer_matmul_params(m) + attention_flops(m, keys)
    return f + (2 * d * v if logits else 0)


def decode_step(m, tp: int, contexts) -> tuple:
    """(flops, bytes) on one chip for one decode step over the active
    slots; ``contexts[i]`` counts the positions slot i attends, its new
    token included."""
    d, _, _, _, _, v, n = _dims(m)
    b = BYTES[m["dtype"]]
    flops = sum(token_flops(m, c, True) for c in contexts) // tp
    weights = param_bytes_per_chip(m, tp) - b * v * d // tp \
        + b * len(contexts) * d          # embedding: one row per token
    kv = kv_bytes_per_token(m, tp) * (sum(contexts) + len(contexts))
    return flops, weights + kv


def prefill_chunk(m, tp: int, start: int, n_real: int,
                  last: bool) -> tuple:
    """(flops, bytes) on one chip for one admission chunk whose ``n_real``
    prompt tokens sit at positions start .. start+n_real-1; ``last``: the
    chunk that ends the prompt, which needs one row of logits."""
    d, _, _, _, _, v, n = _dims(m)
    b = BYTES[m["dtype"]]
    flops = sum(token_flops(m, start + i + 1, False)
                for i in range(n_real))
    if last:
        flops += 2 * d * v
    weights = param_bytes_per_chip(m, tp) - b * v * d * (1 if last else 2) \
        // tp + b * n_real * d
    kv = kv_bytes_per_token(m, tp) * (start + 2 * n_real)
    return flops // tp, weights + kv


def prompt_chunks(n_prompt: int, chunk: int):
    """(start, n_real, last) of each admission chunk of a prompt."""
    starts = range(0, n_prompt, chunk)
    return [(s, min(chunk, n_prompt - s), s + chunk >= n_prompt)
            for s in starts]
