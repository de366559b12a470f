"""Per-layer block: pre-norm temporal mixer + pre-norm FFN, dispatched on
block kind. One function pair (init/apply) covers all seven block kinds so
the decoder can stack heterogeneous patterns uniformly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import nn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models import xlstm as xl
from repro.models.config import ArchConfig
from repro.sharding.api import constrain

_ATTN_KINDS = ("attn", "swa", "mrope")
_SELF_CONTAINED = ("slstm", "mlstm")  # no separate FFN half


def window_for(cfg: ArchConfig, kind: str, force_window: int = 0) -> int:
    if force_window > 0 and kind in _ATTN_KINDS + ("mla",):
        if kind == "swa" and cfg.sliding_window:
            return min(cfg.sliding_window, force_window)
        return force_window
    if kind == "swa":
        return cfg.sliding_window
    return 0


def ffn_init(rng, cfg: ArchConfig, dtype, ffn: str | None = None):
    """The FFN of one layer: ``ffn`` (default ``cfg.ffn_kind``) is
    ``"moe"`` or a dense SwiGLU of width ``cfg.d_ff``."""
    if (ffn or cfg.ffn_kind) == "moe":
        return moe_mod.moe_init(rng, cfg, dtype)
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": nn.normal_init(ks[0], (d, f), std=d ** -0.5, dtype=dtype),
        "w_up": nn.normal_init(ks[1], (d, f), std=d ** -0.5, dtype=dtype),
        "w_down": nn.normal_init(ks[2], (f, d), std=f ** -0.5, dtype=dtype),
    }


def ffn_apply(p, cfg: ArchConfig, x):
    if "router" in p:
        return moe_mod.moe_apply(p, cfg, x)
    cdt = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cdt)
    h = nn.swiglu(xc @ p["w_gate"].astype(cdt), xc @ p["w_up"].astype(cdt))
    h = constrain(h, ("batch", None, "ffn"))
    out = h @ p["w_down"].astype(cdt)
    return out.astype(x.dtype), jnp.zeros((), jnp.float32)


def block_init(rng, cfg: ArchConfig, kind: str, dtype,
               ffn: str | None = None):
    """One layer's params; ``ffn`` overrides ``cfg.ffn_kind`` (a leading
    dense layer of an MoE model)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    p: dict = {"norm1": nn.rmsnorm_init(cfg.d_model, dtype)}
    if kind in _ATTN_KINDS:
        p["mixer"] = attn.gqa_init(k1, cfg, dtype)
    elif kind == "mla":
        p["mixer"] = attn.mla_init(k1, cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = rec.rglru_init(k1, cfg, dtype)
    elif kind == "mlstm":
        p["mixer"] = xl.mlstm_init(k1, cfg, dtype)
    elif kind == "slstm":
        p["mixer"] = xl.slstm_init(k1, cfg, dtype)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if kind not in _SELF_CONTAINED and cfg.ffn_kind != "none":
        p["norm2"] = nn.rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = ffn_init(k2, cfg, dtype, ffn)
    del k3
    return p


def init_block_cache(cfg: ArchConfig, kind: str, batch: int, capacity: int,
                     force_window: int = 0):
    w = window_for(cfg, kind, force_window)
    cap = min(capacity, w) if w else capacity
    kvdt = jnp.dtype(cfg.kv_cache_dtype)
    if kind in _ATTN_KINDS:
        return attn.init_attn_cache(batch, cap, cfg.n_kv_heads, cfg.head_dim,
                                    dtype=kvdt)
    if kind == "mla":
        return attn.init_mla_cache(batch, cap, cfg.mla, dtype=kvdt)
    if kind == "rglru":
        return rec.init_rglru_cache(batch, cfg)
    if kind == "mlstm":
        return xl.init_mlstm_cache(batch, cfg)
    if kind == "slstm":
        return xl.init_slstm_cache(batch, cfg)
    raise ValueError(kind)


def block_apply(p, cfg: ArchConfig, kind: str, x, *, positions, pos3=None,
                cache=None, force_window: int = 0):
    """Returns (x_out, new_cache, aux_loss)."""
    w = window_for(cfg, kind, force_window)
    h = nn.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind in _ATTN_KINDS:
        mix, new_cache = attn.gqa_apply(
            p["mixer"], cfg, h, positions=positions, window=w, cache=cache,
            pos3=pos3 if kind == "mrope" else None)
    elif kind == "mla":
        mix, new_cache = attn.mla_apply(p["mixer"], cfg, h,
                                        positions=positions, cache=cache,
                                        window=w)
    elif kind == "rglru":
        mix, new_cache = rec.rglru_block_apply(p["mixer"], cfg, h,
                                               cache=cache)
    elif kind == "mlstm":
        mix, new_cache = xl.mlstm_block_apply(p["mixer"], cfg, h, cache=cache)
    elif kind == "slstm":
        mix, new_cache = xl.slstm_block_apply(p["mixer"], cfg, h, cache=cache)
    else:
        raise ValueError(kind)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        f, aux = ffn_apply(p["ffn"], cfg,
                           nn.rmsnorm_apply(p["norm2"], x, cfg.norm_eps))
        x = x + f
    x = constrain(x, ("batch", "seq", None))
    return x, new_cache, aux


def block_prefill(p, cfg: ArchConfig, kind: str, x, *, positions, pos3=None,
                  capacity: int = 0, force_window: int = 0):
    """Prefill: like apply but builds a fresh decode cache."""
    w = window_for(cfg, kind, force_window)
    b = x.shape[0]
    h = nn.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind in _ATTN_KINDS:
        cap = min(capacity, w) if w else capacity
        mix, new_cache = attn.gqa_prefill_cache(
            p["mixer"], cfg, h, positions=positions, window=w, capacity=cap,
            pos3=pos3 if kind == "mrope" else None)
    elif kind == "mla":
        cap = min(capacity, w) if w else capacity
        cache = attn.init_mla_cache(b, cap, cfg.mla,
                                    dtype=jnp.dtype(cfg.kv_cache_dtype))
        mix, new_cache = attn.mla_apply(p["mixer"], cfg, h,
                                        positions=positions, cache=cache,
                                        window=w)
    elif kind == "rglru":
        mix, new_cache = rec.rglru_prefill_cache(p["mixer"], cfg, h)
    elif kind == "mlstm":
        cache = xl.init_mlstm_cache(b, cfg)
        mix, new_cache = xl.mlstm_block_apply(p["mixer"], cfg, h, cache=cache)
    elif kind == "slstm":
        cache = xl.init_slstm_cache(b, cfg)
        mix, new_cache = xl.slstm_block_apply(p["mixer"], cfg, h, cache=cache)
    else:
        raise ValueError(kind)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        f, aux = ffn_apply(p["ffn"], cfg,
                           nn.rmsnorm_apply(p["norm2"], x, cfg.norm_eps))
        x = x + f
    x = constrain(x, ("batch", "seq", None))
    return x, new_cache, aux
