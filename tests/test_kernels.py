"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret
mode (the kernel bodies execute on CPU through the Pallas interpreter)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,kv,sq,sk,hd", [
    (1, 4, 4, 128, 128, 32),     # MHA square
    (2, 8, 2, 128, 128, 64),     # GQA 4:1
    (1, 4, 1, 256, 256, 32),     # MQA
    (1, 2, 2, 128, 384, 32),     # cross lengths (prefix cache)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(rng, b, h, kv, sq, sk, hd, dtype):
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = _rand(kq, (b, h, sq, hd), dtype)
    k = _rand(kk, (b, kv, sk, hd), dtype)
    v = _rand(kv_, (b, kv, sk, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [0, 64, 128])
def test_flash_attention_causal_window(rng, window):
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = _rand(kq, (1, 4, 256, 32), jnp.float32)
    k = _rand(kk, (1, 2, 256, 32), jnp.float32)
    v = _rand(kv_, (1, 2, 256, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_blockwise(rng):
    """The Pallas kernel and the model's lax.scan blockwise attention agree."""
    from repro.models.attention import blockwise_attention
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = _rand(kq, (2, 8, 128, 32), jnp.float32)
    k = _rand(kk, (2, 4, 128, 32), jnp.float32)
    v = _rand(kv_, (2, 4, 128, 32), jnp.float32)
    pos = jnp.arange(128)
    got = blockwise_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3),
                              pos, pos, window=0, k_chunk=32)
    want = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got.transpose(0, 2, 1, 3)),
                               np.asarray(want), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,d,chunk,block_d", [
    (1, 128, 128, 64, 64),
    (2, 96, 256, 32, 128),       # s not a multiple of chunk request
    (3, 64, 192, 64, 128),       # d not a multiple of block request
])
def test_rglru_scan_shapes(rng, b, s, d, chunk, block_d):
    ka, kb, kh = jax.random.split(rng, 3)
    a = jax.random.uniform(ka, (b, s, d), minval=0.4, maxval=0.999)
    bb = jax.random.normal(kb, (b, s, d))
    h0 = jax.random.normal(kh, (b, d))
    out = ops.rglru_scan(a, bb, h0, chunk=chunk, block_d=block_d)
    want = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_rglru_scan_matches_associative_scan(rng):
    from repro.models.recurrent import rglru_scan as model_scan
    # build gates through the real parameterization and compare paths
    d = 64
    p = {
        "w_a": jax.random.normal(rng, (d, d)) * 0.05,
        "b_a": jnp.zeros((d,)),
        "w_x": jax.random.normal(jax.random.fold_in(rng, 1), (d, d)) * 0.05,
        "b_x": jnp.zeros((d,)),
        "lam": jnp.ones((d,)),
    }
    xi = jax.random.normal(jax.random.fold_in(rng, 2), (2, 32, d))
    h0 = jnp.zeros((2, d))
    hs, _ = model_scan(p, xi, h0)
    from repro.models.recurrent import rglru_gates
    a, b = rglru_gates(p, xi)
    b = b.at[:, 0].add(a[:, 0] * h0)
    got = ops.rglru_scan(a, b, jnp.zeros((2, d)), chunk=16, block_d=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(hs),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# sLSTM recurrence (VMEM-resident R — §Perf pair 2)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,h,hd,chunk", [
    (1, 64, 4, 32, 16),
    (2, 96, 2, 64, 32),      # s not a multiple of requested chunk
    (2, 32, 1, 128, 32),     # single head
])
def test_slstm_scan_kernel(rng, b, s, h, hd, chunk):
    d = h * hd
    k1, k2 = jax.random.split(rng)
    wx = jax.random.normal(k1, (b, s, 4 * d)) * 0.5
    r = jax.random.normal(k2, (4, h, hd, hd)) * (hd ** -0.5)
    h0 = jnp.zeros((b, d))
    c0 = jnp.zeros((b, d))
    n0 = jnp.zeros((b, d))
    m0 = jnp.full((b, d), -1e30)
    hs, state = ops.slstm_scan(wx, r, h0, c0, n0, m0, chunk=chunk)
    hs_ref, state_ref = ref.slstm_scan_ref(wx, r, h0, c0, n0, m0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_ref),
                               atol=2e-5, rtol=2e-5)
    for a, b_ in zip(state, state_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-5)


def test_slstm_kernel_matches_model_block(rng):
    """The kernel path reproduces the model's _slstm_step scan exactly
    (same gate math through the real parameterization)."""
    from repro.models import xlstm as xl
    from repro.configs import get_config
    cfg = get_config("xlstm-125m", reduced=True).replace(
        compute_dtype="float32")
    d = cfg.d_model
    p = xl.slstm_init(rng, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 32, d)) * 0.3
    # model path
    out_model, _ = xl.slstm_block_apply(p, cfg, x, cache=None)
    # kernel path: wx = x @ w_in + b_in, then the recurrence
    wx = x @ p["w_in"] + p["b_in"]
    h0 = jnp.zeros((2, d))
    m0 = jnp.full((2, d), -1e30)
    hs, _ = ops.slstm_scan(wx, p["r"], h0, h0, h0, m0)
    # re-apply the block's output path (norm + gated MLP)
    from repro.models import nn
    hs_n = nn.rmsnorm_apply({"scale": p["norm_scale"]}, hs.astype(x.dtype))
    up = hs_n @ p["w_up"]
    g, u = jnp.split(up, 2, axis=-1)
    want = (nn.gelu(g) * u) @ p["w_down"]
    np.testing.assert_allclose(np.asarray(out_model), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# fused CC-FedAvg round update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,p,block", [
    (4, 512, 128),
    (8, 1000, 256),      # p not a multiple of requested block
    (1, 256, 256),       # single client
    (3, 509, 512),       # prime P < block: pad-to-tile fallback regression
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cc_delta_update(rng, n, p, block, dtype):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    locals_ = _rand(k1, (n, p), dtype)
    deltas = _rand(k2, (n, p), dtype)
    globals_ = _rand(k3, (p,), dtype)
    train = (jax.random.uniform(k4, (n,)) > 0.5).astype(jnp.float32)
    sel = jnp.ones((n,), jnp.float32)
    d1, g1 = ops.cc_delta_update(locals_, deltas, globals_, train, sel,
                                 block=block)
    d2, g2 = ref.cc_delta_update_ref(locals_, deltas, globals_, train, sel)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(g1, np.float32),
                               np.asarray(g2, np.float32), atol=tol)


@pytest.mark.parametrize("mats", [
    [jnp.float32] * 3,                         # f32 history
    [jnp.float32] * 4,                         # ... with the stale model
    [jnp.float32, jnp.int8, jnp.int8],         # int8 history
    [jnp.float32, jnp.int8, jnp.int8, jnp.float32],
], ids=["f32", "f32-stale", "q8", "q8-stale"])
def test_fused_block_fits_vmem_budget_or_raises(mats):
    """The block is the largest lane-aligned one whose double-buffered
    tiles fit the VMEM budget; past the largest N none does, and the error
    names that N instead of handing Mosaic a kernel it cannot place."""
    from repro.kernels import cc_delta_update as cc
    for n in (1, 8, 64, 512):
        block, p_pad = cc._block_and_pad(11_220_480, n, mats)
        assert block % 128 == 0 and p_pad % block == 0
        assert cc._lane_bytes(n, mats) * block <= cc.VMEM_BUDGET
        assert cc._lane_bytes(n, mats) * (block + 128) > cc.VMEM_BUDGET
    assert cc._block_and_pad(300, 8, mats) == (384, 384)   # capped at P
    assert cc._block_and_pad(4096, 8, mats, block=1000) == (896, 4480)
    n_max = cc.max_clients(mats)
    assert cc._block_and_pad(2 ** 20, n_max, mats)[0] >= 128
    with pytest.raises(ValueError,
                       match=f"largest N for these operands is {n_max}$"):
        cc._block_and_pad(2 ** 20, n_max + 1, mats)


def test_cc_delta_update_equals_engine_round(rng):
    """The fused kernel computes the same update as Algorithm 1 in the
    engine (strategy='cc', all clients selected)."""
    n, p = 4, 256
    k1, k2, k3 = jax.random.split(rng, 3)
    globals_ = jax.random.normal(k1, (p,))
    locals_ = globals_[None] + 0.1 * jax.random.normal(k2, (n, p))
    deltas = 0.05 * jax.random.normal(k3, (n, p))
    train = jnp.array([1.0, 0.0, 1.0, 0.0])
    sel = jnp.ones((n,))
    d_new, g_new = ops.cc_delta_update(locals_, deltas, globals_, train, sel)
    # manual Algorithm 1: Δ_i = train ? local-g : Δ_{t-1}; x' = x + mean Δ
    want_d = jnp.where(train[:, None] > 0, locals_ - globals_[None], deltas)
    want_g = globals_ + jnp.mean(want_d, axis=0)
    np.testing.assert_allclose(np.asarray(d_new), np.asarray(want_d),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_new), np.asarray(want_g),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# strategy-parameterized epilogue update + int8 (q8) quantized history
# ---------------------------------------------------------------------------


def _epilogue_case(rng, n, p, *, with_stale):
    """Random FusedEpilogue-shaped inputs (coefficients in strategy range)."""
    ks = jax.random.split(rng, 9)
    locals_ = jax.random.normal(ks[0], (n, p))
    deltas = 0.1 * jax.random.normal(ks[1], (n, p))
    globals_ = jax.random.normal(ks[2], (p,))
    train = (jax.random.uniform(ks[3], (n,)) > 0.5).astype(jnp.float32)
    agg_w = jax.random.uniform(ks[4], (n,))
    e_replay = jax.random.uniform(ks[5], (n,))
    e_stale = (jax.random.uniform(ks[6], (n,)) if with_stale
               else jnp.zeros((n,)))
    store_scale = jax.random.uniform(ks[7], (n,), minval=0.5, maxval=1.0)
    stale = (0.05 * jax.random.normal(ks[8], (n, p)) if with_stale
             else None)
    denom = jnp.maximum(jnp.sum(agg_w), jnp.float32(1e-12))
    post = jnp.float32(1.25)
    return (locals_, deltas, globals_, train, train, agg_w, e_replay,
            e_stale, store_scale, denom, post, stale)


@pytest.mark.parametrize("n,p,block", [
    (4, 512, 128),
    (8, 1000, 256),
    (3, 509, 512),       # prime P < block
])
@pytest.mark.parametrize("with_stale", [False, True])
def test_cc_epilogue_update_bit_exact_vs_ref(rng, n, p, block, with_stale):
    """The epilogue kernel is pinned BIT-EXACT against the unrolled
    sequential reference — refs are compared under jit (eager XLA makes
    different mul+add contraction choices and is 1 ulp off)."""
    case = _epilogue_case(rng, n, p, with_stale=with_stale)
    d1, g1 = ops.cc_epilogue_update(*case, block=block, interpret=True)
    d2, g2 = jax.jit(ref.cc_epilogue_update_ref)(*case)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


def test_cc_epilogue_identity_equals_legacy_kernel(rng):
    """The legacy 5-arg op is exactly the identity epilogue: agg_w=sel,
    e_replay=1, e_stale=0, store_scale=1, denom=1e-9+Σsel, post=1."""
    n, p = 4, 512
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    locals_ = jax.random.normal(k1, (n, p))
    deltas = 0.1 * jax.random.normal(k2, (n, p))
    globals_ = jax.random.normal(k3, (p,))
    train = (jax.random.uniform(k4, (n,)) > 0.5).astype(jnp.float32)
    sel = jnp.ones((n,), jnp.float32)
    d1, g1 = ops.cc_delta_update(locals_, deltas, globals_, train, sel,
                                 interpret=True)
    d2, g2 = ops.cc_epilogue_update(
        locals_, deltas, globals_, train, train, sel, jnp.ones((n,)),
        jnp.zeros((n,)), jnp.ones((n,)), 1e-9 + jnp.sum(sel),
        jnp.float32(1.0), interpret=True)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


def _q8_case(rng, n, p, *, with_stale):
    from repro.core.compress import quantize_rows
    case = _epilogue_case(rng, n, p, with_stale=with_stale)
    locals_, deltas = case[0], case[1]
    payload, scales = quantize_rows(deltas)
    return (locals_, payload, scales) + case[2:]


@pytest.mark.parametrize("n,p,block", [
    (4, 512, 128),
    (8, 1000, 256),
    (3, 509, 512),       # prime P < block
])
@pytest.mark.parametrize("with_stale", [False, True])
def test_cc_delta_update_q8_bit_exact_vs_ref(rng, n, p, block, with_stale):
    """The int8 dequant→select/aggregate→requant kernel is pinned
    BIT-EXACT (payload, scales AND aggregated global) against the
    sequential quantized reference, compared under jit."""
    import functools
    from repro.kernels.cc_delta_update_q8 import cc_delta_update_q8_fwd
    case = _q8_case(rng, n, p, with_stale=with_stale)
    q1, s1, g1 = jax.jit(functools.partial(
        cc_delta_update_q8_fwd, block=block, interpret=True))(*case)
    q2, s2, g2 = jax.jit(ref.cc_delta_update_q8_ref)(*case)
    assert q1.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


@pytest.mark.parametrize("with_stale", [False, True])
def test_cc_delta_update_q8_jnp_matches_pallas(rng, with_stale):
    """The vectorized XLA path (what ``ops.cc_delta_update_q8`` dispatches
    to off-TPU) produces bit-identical payload/scales to the Pallas
    kernel; only the f32 summation order of the global differs."""
    import functools
    from repro.kernels.cc_delta_update_q8 import (cc_delta_update_q8_fwd,
                                                  cc_delta_update_q8_jnp)
    case = _q8_case(rng, 6, 640, with_stale=with_stale)
    q1, s1, g1 = jax.jit(functools.partial(
        cc_delta_update_q8_fwd, block=256, interpret=True))(*case)
    q2, s2, g2 = jax.jit(cc_delta_update_q8_jnp)(*case)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)


@pytest.mark.parametrize("with_stale", [False, True])
def test_q8_chunked_row_maxima_path_bit_exact(rng, with_stale):
    """Above ``_MX_MIN_COLS`` the jnp path switches to the chunked
    accumulator row-maxima (with upd-row skipping and a strided tail) —
    max is exactly associative, so payload/scales must stay bit-identical
    to the plain-reduce formula and to the Pallas kernel."""
    import functools
    from repro.kernels import cc_delta_update_q8 as q8
    n, p = 5, q8._MX_MIN_COLS + 509        # chunk loop + odd tail
    assert p >= q8._MX_MIN_COLS
    case = list(_q8_case(rng, n, p, with_stale=with_stale))
    case[5] = jnp.array([1.0, 0.0, 1.0, 1.0, 0.0])       # upd mix: skip path
    q1, s1, g1 = jax.jit(functools.partial(
        q8.cc_delta_update_q8_fwd, block=16384, interpret=True))(*case)
    q2, s2, g2 = jax.jit(q8.cc_delta_update_q8_jnp)(*case)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)
    # and the chunked maxima themselves equal the plain reduce, bit for bit
    mx_plain = jnp.max(jnp.abs(case[0] - case[3][None]), axis=1)
    mx_chunk = jax.jit(q8._row_maxima)(case[0], case[3], case[5])
    upd = np.asarray(case[5]) > 0
    np.testing.assert_array_equal(np.asarray(mx_chunk)[upd],
                                  np.asarray(mx_plain)[upd])


def test_q8_non_update_rows_keep_payload(rng):
    """Rows with upd=0 must keep their int8 payload byte-identical (no
    requantization drift round over round) — only the scale is folded by
    ``store_scale`` (the decay-in-scale trick)."""
    n, p = 4, 512
    (locals_, payload, scales, _, _, _, agg_w, e_replay, e_stale,
     _, denom, post, _) = _q8_case(rng, n, p, with_stale=False)
    upd = jnp.array([1.0, 0.0, 1.0, 0.0])
    store = jnp.array([1.0, 0.9, 1.0, 1.0])
    q, s, _ = ops.cc_delta_update_q8(
        locals_, payload, scales, jnp.zeros((p,)), upd, upd, agg_w,
        e_replay, e_stale, store, denom, post)
    np.testing.assert_array_equal(np.asarray(q[1]), np.asarray(payload[1]))
    np.testing.assert_array_equal(np.asarray(q[3]), np.asarray(payload[3]))
    np.testing.assert_allclose(np.asarray(s[1]),
                               np.asarray(scales[1]) * 0.9, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s[3]), np.asarray(scales[3]))
    assert not np.array_equal(np.asarray(q[0]), np.asarray(payload[0]))
