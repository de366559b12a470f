"""Attention blocks: GQA / sliding-window / M-RoPE / MLA, with a
memory-bounded blockwise (online-softmax) formulation.

Un-fused ``softmax(QKᵀ)V`` materializes an (Sq × Sk) score tensor per head —
at the assigned shapes (4k train, 32k prefill) that is tens of GB per device,
so the framework's reference attention is *blockwise*: a ``lax.scan`` over
key/value chunks carrying the online-softmax state ``(m, l, acc)``. This is
the same algorithm the Pallas flash kernel (:mod:`repro.kernels.flash_attention`)
implements at the VMEM-tile level; XLA sees only chunk-sized intermediates.

Numerical convention for masking: masked logits are set to a finite
``_MASK_VALUE`` (−1e30) and the running max starts there, so fully-masked
chunks (e.g. out-of-window blocks processed before the first in-window block)
contribute weight that is exactly flushed by the next real block's
renormalization — no NaNs, no ±inf arithmetic.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import nn
from repro.models.config import ArchConfig, MLAConfig
from repro.sharding.api import constrain
from repro.utils.trace import MLA, scope

_MASK_VALUE = -1.0e30


# ---------------------------------------------------------------------------
# mask / position helpers
# ---------------------------------------------------------------------------


def _band_mask(q_pos, k_pos, window: int, k_valid=None):
    """(Sq, Sk) bool mask: causal ∧ in-window ∧ key-slot-valid."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    m &= k_pos[None, :] >= 0  # negative positions mark empty cache slots
    if k_valid is not None:
        m &= k_valid[None, :]
    return m


class AttnCache(NamedTuple):
    """Decode-time KV cache (ring buffer when windowed)."""
    k: jax.Array    # (B, C, Kv, hd)
    v: jax.Array    # (B, C, Kv, hd)
    pos: jax.Array  # (C,) absolute position held by each slot; -1 = empty
    idx: jax.Array  # () next write offset (monotonic token counter)


def init_attn_cache(batch: int, capacity: int, n_kv: int, head_dim: int,
                    dtype=jnp.bfloat16) -> AttnCache:
    return AttnCache(
        k=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        pos=jnp.full((capacity,), -1, jnp.int32),
        idx=jnp.zeros((), jnp.int32),
    )


def cache_write(cache: AttnCache, k_new, v_new, positions) -> AttnCache:
    """Write S_new tokens at ring slots (idx + arange) % capacity.

    The 1-token decode write uses dynamic_update_slice — a scatter here
    makes GSPMD replicate/re-shard the whole cache every step (was the
    entire decode collective term, §Perf D1)."""
    cap = cache.pos.shape[0]
    s_new = k_new.shape[1]
    if s_new == 1:
        slot = (cache.idx % cap).astype(jnp.int32)
        k = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k_new.astype(cache.k.dtype), slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_new.astype(cache.v.dtype), slot, axis=1)
        pos = jax.lax.dynamic_update_slice(
            cache.pos, positions.astype(jnp.int32), (slot,))
        return AttnCache(k=k, v=v, pos=pos, idx=cache.idx + 1)
    slots = (cache.idx + jnp.arange(s_new, dtype=jnp.int32)) % cap
    k = cache.k.at[:, slots].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[:, slots].set(v_new.astype(cache.v.dtype))
    pos = cache.pos.at[slots].set(positions.astype(jnp.int32))
    return AttnCache(k=k, v=v, pos=pos, idx=cache.idx + s_new)


# ---------------------------------------------------------------------------
# blockwise attention core
# ---------------------------------------------------------------------------


def blockwise_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                        k_chunk: int = 1024, scale: float | None = None,
                        logit_softcap: float = 0.0):
    """Online-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Sk, Kv, hd); H = Kv·G (GQA).
    q_pos: (Sq,), k_pos: (Sk,) absolute positions (−1 = invalid slot).
    Returns (B, Sq, H, hd) in q.dtype.
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    # keep q/k in their (bf16) dtype and accumulate the dot in f32 — the
    # MXU path; casting q/k to f32 first doubles HBM + gather traffic.
    # Heads stay FLAT (GQA K/V broadcast inside the chunk, fused by XLA):
    # a (KV, G) head split cannot express 16-way sharding when KV < 16,
    # which forced GSPMD to replicate the online-softmax carry (§Perf).
    qg = q * jnp.asarray(scale, q.dtype)             # (B,Sq,H,hd)

    def expand(t):   # (B,C,KV,hd) -> (B,C,H,hd)
        if g == 1:
            return t
        return jnp.repeat(t, g, axis=2)

    def chunk_scores(ks, kp):
        s = jnp.einsum("bqhd,bchd->bhqc", qg, expand(ks),
                       preferred_element_type=jnp.float32)
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        mask = _band_mask(q_pos, kp, window)  # (Sq, C)
        s = jnp.where(mask[None, None, :, :], s, _MASK_VALUE)
        return s

    if sk <= k_chunk:
        s = chunk_scores(k, k_pos)                   # (B,H,Sq,C)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.maximum(m, _MASK_VALUE)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bhqc,bchd->bhqd", p.astype(v.dtype), expand(v),
                         preferred_element_type=jnp.float32)
        out = out / jnp.maximum(l, 1e-30)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)

    while sk % k_chunk:  # largest divisor ≤ requested chunk
        k_chunk -= 1
    n_chunks = sk // k_chunk
    k_r = k.reshape(b, n_chunks, k_chunk, kv, hd)
    v_r = v.reshape(b, n_chunks, k_chunk, kv, hd)
    kp_r = k_pos.reshape(n_chunks, k_chunk)

    def body(carry, xs):
        m, l, acc = carry
        ks, vs, kp = xs
        s = chunk_scores(ks, kp)                     # (B,H,Sq,C)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # (B,H,Sq)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqc,bchd->bhqd", p.astype(vs.dtype), expand(vs),
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    if _REMAT_CHUNKS:
        body = jax.checkpoint(body)
    # constrain the carry to q's sharding — an unconstrained carry makes
    # GSPMD replicate it and re-gather q every chunk (§Perf iteration 3)
    m0 = constrain(jnp.full((b, h, sq), _MASK_VALUE, jnp.float32),
                   ("batch", "heads", "qseq"))
    l0 = constrain(jnp.zeros((b, h, sq), jnp.float32),
                   ("batch", "heads", "qseq"))
    acc0 = constrain(jnp.zeros((b, h, sq, hd), jnp.float32),
                     ("batch", "heads", "qseq", None))
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (k_r.transpose(1, 0, 2, 3, 4), v_r.transpose(1, 0, 2, 3, 4), kp_r))
    out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,H,Sq,hd)
    out = out.transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


# recompute chunk scores in the backward pass (flash-like memory); module
# flag so tests can disable it when probing gradients chunk-by-chunk.
_REMAT_CHUNKS = True


# ---------------------------------------------------------------------------
# GQA attention block (covers 'attn', 'swa', 'mrope')
# ---------------------------------------------------------------------------


def gqa_init(rng, cfg: ArchConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": nn.normal_init(ks[0], (d, h * hd), std=d ** -0.5, dtype=dtype),
        "wk": nn.normal_init(ks[1], (d, kv * hd), std=d ** -0.5, dtype=dtype),
        "wv": nn.normal_init(ks[2], (d, kv * hd), std=d ** -0.5, dtype=dtype),
        "wo": nn.normal_init(ks[3], (h * hd, d), std=(h * hd) ** -0.5,
                             dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = nn.rmsnorm_init(hd, dtype)
        p["k_norm"] = nn.rmsnorm_init(hd, dtype)
    return p


def _rope_for(cfg: ArchConfig, positions, pos3=None):
    """cos/sin for given positions; M-RoPE when cfg.mrope_sections set."""
    if cfg.mrope_sections:
        assert pos3 is not None, "mrope needs (3,B,S) positions"
        return nn.mrope_cos_sin(pos3, cfg.head_dim, cfg.mrope_sections,
                                cfg.rope_theta)
    cos, sin = nn.rope_cos_sin(positions[None, :], cfg.head_dim,
                               cfg.rope_theta)
    return cos, sin  # (1, S, hd/2) broadcasting over batch


def gqa_apply(p, cfg: ArchConfig, x, *, positions, window: int,
              cache: AttnCache | None, pos3=None):
    """x: (B, S, D). Returns (out, new_cache)."""
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = jnp.dtype(cfg.compute_dtype)
    xq = x.astype(cdt)
    q = (xq @ p["wq"].astype(cdt)).reshape(b, s, h, hd)
    k = (xq @ p["wk"].astype(cdt)).reshape(b, s, kv, hd)
    v = (xq @ p["wv"].astype(cdt)).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = nn.rmsnorm_apply(p["q_norm"], q)
        k = nn.rmsnorm_apply(p["k_norm"], k)
    cos, sin = _rope_for(cfg, positions, pos3)
    q = nn.apply_rope(q, cos, sin)
    k = nn.apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "qseq", "heads", "kv_head_dim"))
    k = constrain(k, ("batch", None, "kv_heads", "kv_head_dim"))
    v = constrain(v, ("batch", None, "kv_heads", "kv_head_dim"))

    if cache is None:
        out = blockwise_attention(
            q, k, v, positions, positions, window=window,
            k_chunk=min(cfg.attn_k_chunk, s), scale=hd ** -0.5)
        new_cache = None
    else:
        cache = cache_write(cache, k, v, positions)
        cap = cache.k.shape[1]
        out = blockwise_attention(
            q, cache.k.astype(cdt), cache.v.astype(cdt),
            positions, cache.pos, window=window,
            k_chunk=cap if s == 1 else min(cfg.attn_k_chunk, cap),
            scale=hd ** -0.5)
        new_cache = cache
    out = constrain(out, ("batch", None, "heads", None))
    y = out.reshape(b, s, h * hd) @ p["wo"].astype(cdt)
    return y.astype(x.dtype), new_cache


def gqa_prefill_cache(p, cfg: ArchConfig, x, *, positions, window: int,
                      capacity: int, pos3=None):
    """Prefill: attention over the full prompt + build the decode cache.

    The cache keeps only the last ``capacity`` prompt tokens (ring-buffer
    semantics: token at position p lives in slot p % capacity), so windowed
    caches stay window-sized even for 32k prompts.
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = jnp.dtype(cfg.compute_dtype)
    xq = x.astype(cdt)
    q = (xq @ p["wq"].astype(cdt)).reshape(b, s, h, hd)
    k = (xq @ p["wk"].astype(cdt)).reshape(b, s, kv, hd)
    v = (xq @ p["wv"].astype(cdt)).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = nn.rmsnorm_apply(p["q_norm"], q)
        k = nn.rmsnorm_apply(p["k_norm"], k)
    cos, sin = _rope_for(cfg, positions, pos3)
    q = nn.apply_rope(q, cos, sin)
    k = nn.apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "qseq", "heads", "kv_head_dim"))
    k = constrain(k, ("batch", None, "kv_heads", "kv_head_dim"))
    v = constrain(v, ("batch", None, "kv_heads", "kv_head_dim"))
    if cfg.use_pallas and s % 128 == 0 and (window % 128 == 0):
        # prefill is forward-only and positions are contiguous — the
        # Pallas flash kernel applies directly (interpret mode off-TPU)
        from repro.kernels import ops as kops
        out = kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=window
        ).transpose(0, 2, 1, 3)
    else:
        out = blockwise_attention(q, k, v, positions, positions,
                                  window=window,
                                  k_chunk=min(cfg.attn_k_chunk, s),
                                  scale=hd ** -0.5)
    out = constrain(out, ("batch", None, "heads", None))
    y = out.reshape(b, s, h * hd) @ p["wo"].astype(cdt)
    # build the decode cache from the tail of the prompt
    tail = min(s, capacity)
    cache = init_attn_cache(b, capacity, kv, hd,
                            dtype=jnp.dtype(cfg.kv_cache_dtype))
    cache = cache._replace(idx=jnp.asarray(s - tail, jnp.int32))
    cache = cache_write(cache, k[:, s - tail:], v[:, s - tail:],
                        positions[s - tail:])
    return y.astype(x.dtype), cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------


class MLACache(NamedTuple):
    ckv: jax.Array    # (B, C, d_c) compressed latent (already RMS-normed)
    krope: jax.Array  # (B, C, rope_dim) shared roped key
    pos: jax.Array    # (C,)
    idx: jax.Array    # ()


def init_mla_cache(batch: int, capacity: int, mla: MLAConfig,
                   dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        ckv=jnp.zeros((batch, capacity, mla.kv_lora_rank), dtype),
        krope=jnp.zeros((batch, capacity, mla.qk_rope_dim), dtype),
        pos=jnp.full((capacity,), -1, jnp.int32),
        idx=jnp.zeros((), jnp.int32),
    )


def _mla_cache_write(cache: MLACache, ckv, k_rope, positions) -> MLACache:
    """Ring write; 1-token decode uses dynamic_update_slice (a scatter
    forces GSPMD to re-shard the whole latent cache per step — §Perf D1)."""
    cap = cache.pos.shape[0]
    s = ckv.shape[1]
    if s == 1:
        slot = (cache.idx % cap).astype(jnp.int32)
        return MLACache(
            ckv=jax.lax.dynamic_update_slice_in_dim(
                cache.ckv, ckv.astype(cache.ckv.dtype), slot, axis=1),
            krope=jax.lax.dynamic_update_slice_in_dim(
                cache.krope, k_rope.astype(cache.krope.dtype), slot,
                axis=1),
            pos=jax.lax.dynamic_update_slice(
                cache.pos, positions.astype(jnp.int32), (slot,)),
            idx=cache.idx + 1)
    slots = (cache.idx + jnp.arange(s, dtype=jnp.int32)) % cap
    return MLACache(
        ckv=cache.ckv.at[:, slots].set(ckv.astype(cache.ckv.dtype)),
        krope=cache.krope.at[:, slots].set(
            k_rope.astype(cache.krope.dtype)),
        pos=cache.pos.at[slots].set(positions.astype(jnp.int32)),
        idx=cache.idx + s)


def mla_init(rng, cfg: ArchConfig, dtype):
    """MLA weights. With ``q_lora_rank`` the query goes through a low-rank
    ``wq_a`` → ``q_norm`` → ``wq_b`` (MiniCPM3); without (Moonlight), one
    ``wq`` maps ``d_model`` to every head's query directly (``q_proj``)."""
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(rng, 7)
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank is None:
        p = {"wq": nn.normal_init(ks[0], (d, h * qk_dim), std=d ** -0.5,
                                  dtype=dtype)}
    else:
        p = {
            "wq_a": nn.normal_init(ks[0], (d, m.q_lora_rank), std=d ** -0.5,
                                   dtype=dtype),
            "q_norm": nn.rmsnorm_init(m.q_lora_rank, dtype),
            "wq_b": nn.normal_init(ks[1], (m.q_lora_rank, h * qk_dim),
                                   std=m.q_lora_rank ** -0.5, dtype=dtype),
        }
    p.update({
        "wkv_a": nn.normal_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim),
                                std=d ** -0.5, dtype=dtype),
        "kv_norm": nn.rmsnorm_init(m.kv_lora_rank, dtype),
        "wk_b": nn.normal_init(ks[3], (m.kv_lora_rank, h * m.qk_nope_dim),
                               std=m.kv_lora_rank ** -0.5, dtype=dtype),
        "wv_b": nn.normal_init(ks[4], (m.kv_lora_rank, h * m.v_head_dim),
                               std=m.kv_lora_rank ** -0.5, dtype=dtype),
        "wo": nn.normal_init(ks[5], (h * m.v_head_dim, d),
                             std=(h * m.v_head_dim) ** -0.5, dtype=dtype),
    })
    return p


def _mla_qkv(p, cfg: ArchConfig, x, positions):
    """Shared projections. Returns q_nope, q_rope, ckv_n, k_rope."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    cdt = jnp.dtype(cfg.compute_dtype)
    xq = x.astype(cdt)
    if "wq" in p:                            # no q_lora: q_proj directly
        q = xq @ p["wq"].astype(cdt)
    else:
        qa = nn.rmsnorm_apply(p["q_norm"], xq @ p["wq_a"].astype(cdt),
                              cfg.norm_eps)
        q = qa @ p["wq_b"].astype(cdt)
    q = q.reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    kv_a = xq @ p["wkv_a"].astype(cdt)
    ckv = nn.rmsnorm_apply(p["kv_norm"], kv_a[..., : m.kv_lora_rank],
                           cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:]
    cos, sin = nn.rope_cos_sin(positions[None, :], m.qk_rope_dim,
                               cfg.rope_theta)
    q_rope = nn.apply_rope(q_rope, cos, sin)
    k_rope = nn.apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return q_nope, q_rope, ckv, k_rope


def mla_apply(p, cfg: ArchConfig, x, *, positions,
              cache: MLACache | None, window: int = 0):
    """MLA attention, in the ``fed.mla`` device scope. Training/prefill
    uses the naive expanded form; decode uses the weight-absorbed latent
    form when ``cfg.mla.absorb``. Any projection may be a LoRA-adapted
    weight (:class:`repro.models.nn.Adapted`); absorbed decode merges it."""
    with scope(MLA):
        return _mla_apply(p, cfg, x, positions=positions, cache=cache,
                          window=window)


def _mla_apply(p, cfg: ArchConfig, x, *, positions,
               cache: MLACache | None, window: int = 0):
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    cdt = jnp.dtype(cfg.compute_dtype)
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, cfg, x, positions)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5

    if cache is not None and m.absorb and s == 1:
        # ---- absorbed decode: attend in latent space ------------------
        cache = _mla_cache_write(cache, ckv, k_rope, positions)
        wk_b = nn.dense_weight(p["wk_b"]).astype(cdt).reshape(
            m.kv_lora_rank, h, m.qk_nope_dim)
        # q_eff[h] = q_nope[h] @ wk_b[:,h,:]^T  -> latent-dim query
        q_lat = jnp.einsum("bshn,chn->bshc", q_nope, wk_b)
        ckv_c = cache.ckv.astype(jnp.float32)
        s_lat = jnp.einsum("bshc,btc->bhst", q_lat.astype(jnp.float32),
                           ckv_c)
        s_rope = jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                            cache.krope.astype(jnp.float32))
        logits = (s_lat + s_rope) * scale
        mask = _band_mask(positions, cache.pos, window)
        logits = jnp.where(mask[None, None, :, :], logits, _MASK_VALUE)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhst,btc->bshc", probs, ckv_c)  # (B,S,H,d_c)
        wv_b = nn.dense_weight(p["wv_b"]).astype(cdt).reshape(
            m.kv_lora_rank, h, m.v_head_dim)
        out = jnp.einsum("bshc,chv->bshv", o_lat.astype(cdt), wv_b)
        y = out.reshape(b, s, h * m.v_head_dim) @ p["wo"].astype(cdt)
        return y.astype(x.dtype), cache

    # ---- naive expanded form (train / prefill) ------------------------
    if cache is not None:
        cache = _mla_cache_write(cache, ckv, k_rope, positions)
        ckv_all = cache.ckv.astype(cdt)
        krope_all = cache.krope.astype(cdt)
        k_pos = cache.pos
    else:
        ckv_all, krope_all, k_pos = ckv, k_rope, positions
    t = ckv_all.shape[1]
    k_nope = (ckv_all @ p["wk_b"].astype(cdt)).reshape(b, t, h,
                                                        m.qk_nope_dim)
    v_full = (ckv_all @ p["wv_b"].astype(cdt)).reshape(b, t, h,
                                                       m.v_head_dim)
    # pad v to qk dim so we can reuse blockwise_attention, then slice back
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_all[:, :, None, :],
                                  k_nope.shape[:3] + (m.qk_rope_dim,))],
        axis=-1)
    v_pad = jnp.pad(v_full, ((0, 0), (0, 0), (0, 0),
                             (0, qk_dim - m.v_head_dim)))
    # same attention sharding policy as the GQA path (§Perf B1/B3):
    # context-parallel q when heads don't divide TP, K/V replicated
    q_full = constrain(q_full, ("batch", "qseq", "heads", None))
    k_full = constrain(k_full, ("batch", None, "kv_heads", None))
    v_pad = constrain(v_pad, ("batch", None, "kv_heads", None))
    sk = k_full.shape[1]
    chunk = min(cfg.attn_k_chunk, sk)
    out = blockwise_attention(q_full, k_full, v_pad, positions, k_pos,
                              window=window, k_chunk=chunk, scale=scale)
    out = out[..., : m.v_head_dim]
    y = out.reshape(b, s, h * m.v_head_dim) @ p["wo"].astype(cdt)
    return y.astype(x.dtype), cache
