"""Pallas kernel validation: interpret-mode shape/dtype sweeps against the
pure-jnp oracles, plus hypothesis property tests on the invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pure-pytest fallback (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref,
                                            paged_decode_attention)
from repro.kernels.rwkv6_scan import rwkv6_scan, rwkv6_scan_ref
from repro.kernels.ssm_scan import ssm_scan, ssm_scan_ref

rng = np.random.default_rng(0)


def _tol(dt):
    return 3e-2 if dt == jnp.bfloat16 else 3e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    (2, 4, 2, 128, 128, 64, True, 0, jnp.float32),
    (1, 4, 1, 200, 200, 64, True, 0, jnp.float32),   # ragged pad
    (2, 2, 2, 256, 256, 128, True, 64, jnp.bfloat16),  # sliding window
    (1, 8, 2, 128, 384, 64, False, 0, jnp.float32),  # non-causal (encoder)
    (1, 2, 2, 128, 128, 64, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES,
                         ids=[f"case{i}" for i in range(len(FLASH_CASES))])
def test_flash_attention(case):
    B, Hq, Hkv, Sq, Skv, hd, causal, window, dt = case
    q = jnp.asarray(rng.standard_normal((B, Hq, Sq, hd)), dt)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Skv, hd)), dt)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Skv, hd)), dt)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dt), rtol=_tol(dt))


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (4, 4, 2, 512, 64, 0, jnp.float32),
    (3, 8, 1, 300, 128, 0, jnp.float32),
    (8, 2, 2, 1024, 64, 128, jnp.bfloat16),
    (5, 6, 2, 256, 64, 0, jnp.float32),
]


@pytest.mark.parametrize("case", DECODE_CASES,
                         ids=[f"case{i}" for i in range(len(DECODE_CASES))])
def test_decode_attention(case):
    B, Hq, Hkv, S, hd, window, dt = case
    q = jnp.asarray(rng.standard_normal((B, Hq, hd)), dt)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), dt)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), dt)
    pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
    out = decode_attention(q, k, v, pos, window=window, interpret=True)
    ref = decode_attention_ref(q, k, v, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dt), rtol=_tol(dt))


@given(st.integers(1, 6), st.integers(0, 255))
@settings(max_examples=10, deadline=None)
def test_decode_attention_position_property(b_seed, pos_val):
    """Tokens beyond position must not influence the output."""
    B, Hq, Hkv, S, hd = 2, 2, 1, 256, 64
    r = np.random.default_rng(b_seed)
    q = jnp.asarray(r.standard_normal((B, Hq, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(r.standard_normal((B, S, Hkv, hd)), jnp.float32)
    pos = jnp.full((B,), pos_val, jnp.int32)
    out1 = decode_attention(q, k, v, pos, interpret=True)
    # scrub everything past pos: output must be identical
    mask = (jnp.arange(S) <= pos_val)[None, :, None, None]
    out2 = decode_attention(q, jnp.where(mask, k, 999.0),
                            jnp.where(mask, v, -999.0), pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention (live blocks read in place through the table)
# ---------------------------------------------------------------------------

# (B, U, g, lengths, window, layer of n_layers, share, dtype); the pool is a
# stacked (n_layers*n_blocks, bs, U, hd) one and the table is offset to the
# layer's blocks, as the layer loop passes them.  ``share``: slot 1 reads
# slot 0's first two blocks (a shared prefix).  Length 0 is an inactive
# slot, its row on the trash block.
PAGED_CASES = [
    (4, 4, 8, (1, 16, 17, 2047), 0, (0, 1), False, jnp.bfloat16),
    (4, 2, 12, (2047, 17, 16, 1), 0, (1, 2), False, jnp.bfloat16),
    (3, 2, 4, (300, 64, 5), 128, (0, 1), False, jnp.float32),
    (4, 4, 2, (40, 40, 0, 33), 0, (2, 3), True, jnp.float32),
    (4, 2, 8, (128, 0, 129, 256), 48, (1, 2), True, jnp.float32),
    (4, 2, 8, (0, 0, 33, 0), 0, (0, 1), False, jnp.float32),
]
PAGED_BS, PAGED_MB, PAGED_HD = 16, 128, 128


def _paged_problem(B, U, g, lengths, layer, share, dt, seed=0):
    """Query, stacked pools, the layer's offset table and the lengths."""
    li, n_layers = layer
    r = np.random.default_rng(seed)
    bs, mb, hd = PAGED_BS, PAGED_MB, PAGED_HD
    nb = B * mb + 1                               # per layer; block 0 trash
    q = jnp.asarray(r.standard_normal((B, U * g, hd)), dt)
    k = jnp.asarray(r.standard_normal((n_layers * nb, bs, U, hd)), dt)
    v = jnp.asarray(r.standard_normal((n_layers * nb, bs, U, hd)), dt)
    tbl = (1 + r.permutation(nb - 1)[:B * mb]).reshape(B, mb)
    lengths = np.asarray(lengths, np.int32)
    tbl[lengths == 0] = 0                         # inactive: trash row
    if share:
        tbl[1, :2] = tbl[0, :2]
    tbl = jnp.asarray(tbl + li * nb, jnp.int32)
    return q, k, v, tbl, jnp.asarray(lengths)


@pytest.mark.parametrize("case", PAGED_CASES,
                         ids=[f"case{i}" for i in range(len(PAGED_CASES))])
def test_paged_decode_attention(case):
    """The kernel against the gathered XLA path (its reference) on the
    same pool and table: every live slot's output; an inactive slot reads
    nothing and returns zeros."""
    from repro.models.layers import paged_attention_gathered
    B, U, g, lengths, window, layer, share, dt = case
    q, k, v, tbl, lens = _paged_problem(B, U, g, lengths, layer, share, dt)
    out = paged_decode_attention(q, k, v, tbl, lens, window=window,
                                 interpret=True)
    ref = paged_attention_gathered(q[:, None], k, v, tbl, lens - 1, g=g,
                                   window=window)[:, 0]
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=_tol(dt), rtol=_tol(dt))
    assert not np.asarray(out, np.float32)[~live].any()


@pytest.mark.parametrize("case", [PAGED_CASES[0], PAGED_CASES[4]],
                         ids=["ragged", "window_shared"])
def test_paged_decode_attention_ignores_unread_blocks(case):
    """Scribbling on every block no slot reads (the trash block, other
    layers' blocks, table entries past each length or before the window)
    leaves the output bitwise unchanged."""
    B, U, g, lengths, window, layer, share, dt = case
    q, k, v, tbl, lens = _paged_problem(B, U, g, lengths, layer, share, dt,
                                        seed=3)
    out1 = paged_decode_attention(q, k, v, tbl, lens, window=window,
                                  interpret=True)
    t, n = np.asarray(tbl), np.asarray(lens)
    hi = -(-n // PAGED_BS)
    lo = np.maximum(n - window, 0) // PAGED_BS if window else 0 * n
    read = np.unique(np.concatenate(
        [t[b, lo[b]:hi[b]] for b in range(B)]))
    dead = np.setdiff1d(np.arange(k.shape[0]), read)
    k2 = k.at[dead].set(jnp.nan)
    v2 = v.at[dead].set(jnp.nan)
    out2 = paged_decode_attention(q, k2, v2, tbl, lens, window=window,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# rwkv6 chunked scan
# ---------------------------------------------------------------------------

RWKV_CASES = [(2, 128, 2, 64, 64), (1, 100, 3, 64, 32), (2, 64, 1, 32, 64)]


@pytest.mark.parametrize("case", RWKV_CASES,
                         ids=[f"case{i}" for i in range(len(RWKV_CASES))])
def test_rwkv6_scan(case):
    B, T, H, hd, chunk = case
    r = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    logw = -jnp.exp(jnp.asarray(rng.uniform(-6, -0.5, (B, T, H, hd)),
                                jnp.float32))
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32) * 0.1
    s0 = jnp.asarray(rng.standard_normal((B, H, hd, hd)), jnp.float32) * 0.1
    y, sf = rwkv6_scan(r, k, v, logw, u, s0, chunk=chunk, interpret=True)
    yr, sfr = rwkv6_scan_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sfr), atol=2e-4,
                               rtol=1e-3)


def test_rwkv6_chunked_model_form_matches_step_exact():
    """The model layer's chunked jnp form is itself oracle-consistent."""
    from repro.models.rwkv import rwkv_scan_chunked
    B, T, H, hd = 2, 96, 2, 32
    r = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    logw = -jnp.exp(jnp.asarray(rng.uniform(-6, -0.5, (B, T, H, hd)),
                                jnp.float32))
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32) * 0.1
    y1, s1 = rwkv_scan_chunked(r, k, v, logw, u, chunk=32)
    y2, s2 = rwkv6_scan_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

SSM_CASES = [(2, 128, 128, 16, 64, 128), (1, 100, 64, 8, 32, 64),
             (2, 64, 200, 16, 64, 128)]


@pytest.mark.parametrize("case", SSM_CASES,
                         ids=[f"case{i}" for i in range(len(SSM_CASES))])
def test_ssm_scan(case):
    B, T, Ci, S, ct, bc = case
    x = jnp.asarray(rng.standard_normal((B, T, Ci)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, T, Ci)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, T, S)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, T, S)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4, (Ci, S)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((B, Ci, S)), jnp.float32) * 0.1
    y, hf = ssm_scan(x, dt, b, c, a, h0, chunk_t=ct, block_c=bc,
                     interpret=True)
    yr, hfr = ssm_scan_ref(x, dt, b, c, a, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr), atol=1e-4,
                               rtol=1e-4)


@given(st.integers(0, 10))
@settings(max_examples=8, deadline=None)
def test_ssm_scan_state_chaining(seed):
    """Scanning [0:T] equals scanning [0:T/2] then [T/2:T] with the carried
    state — the invariant elastic restart relies on."""
    B, T, Ci, S = 1, 64, 64, 8
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((B, T, Ci)), jnp.float32)
    dt = jnp.asarray(r.uniform(0.001, 0.1, (B, T, Ci)), jnp.float32)
    b = jnp.asarray(r.standard_normal((B, T, S)), jnp.float32)
    c = jnp.asarray(r.standard_normal((B, T, S)), jnp.float32)
    a = -jnp.asarray(r.uniform(0.5, 4, (Ci, S)), jnp.float32)
    y_full, h_full = ssm_scan_ref(x, dt, b, c, a)
    h = T // 2
    y1, s1 = ssm_scan_ref(x[:, :h], dt[:, :h], b[:, :h], c[:, :h], a)
    y2, s2 = ssm_scan_ref(x[:, h:], dt[:, h:], b[:, h:], c[:, h:], a, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(h_full),
                               atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused grouped expert FFN (MoE dispatch path)
# ---------------------------------------------------------------------------

from repro.kernels.moe_gemm import moe_expert_ffn, moe_expert_ffn_ref

MOE_CASES = [(4, 128, 64, 128, jnp.float32), (2, 100, 128, 200, jnp.float32),
             (8, 256, 64, 96, jnp.bfloat16)]


@pytest.mark.parametrize("case", MOE_CASES,
                         ids=[f"case{i}" for i in range(len(MOE_CASES))])
def test_moe_expert_ffn(case):
    E, C, D, F, dt = case
    x = jnp.asarray(rng.standard_normal((E, C, D)), dt)
    wg = jnp.asarray(rng.standard_normal((E, D, F)) * 0.05, dt)
    wu = jnp.asarray(rng.standard_normal((E, D, F)) * 0.05, dt)
    wd = jnp.asarray(rng.standard_normal((E, F, D)) * 0.05, dt)
    out = moe_expert_ffn(x, wg, wu, wd, interpret=True)
    ref = moe_expert_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dt), rtol=_tol(dt))


# ---------------------------------------------------------------------------
# quantized-collective pack/unpack (ar_quant wire format)
# ---------------------------------------------------------------------------

from repro.kernels.rd_allreduce.quant import quantize_pack, unpack_dequant
from repro.kernels.rd_allreduce.quant_kernel import (quantize_pack_pallas,
                                                     unpack_dequant_pallas)

QP_CASES = [(8, 128, 4, 512), (8, 64, 1, 256), (4, 64, 4, 384),
            (4, 128, 2, 128)]


@pytest.mark.parametrize("case", QP_CASES,
                         ids=[f"b{b}g{g}" for b, g, _, _ in QP_CASES])
def test_quant_pack_kernel_matches_reference(case):
    """The fused Pallas pack/unpack is bit-for-bit the jnp reference: same
    int8 payload (nibble layout included), same bf16 scales, same f32
    dequant — interpret mode, both bit widths."""
    bits, group, R, D = case
    x = jnp.asarray(rng.standard_normal((R, D)) * 3.0, jnp.float32)
    q_ref, s_ref = quantize_pack(x, bits, group)
    q_k, s_k = quantize_pack_pallas(x, bits=bits, group=group,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s_k, np.float32),
                                  np.asarray(s_ref, np.float32))
    d_ref = unpack_dequant(q_ref, s_ref, bits, group)
    d_k = unpack_dequant_pallas(q_k, s_k, bits=bits, group=group,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_ref))
