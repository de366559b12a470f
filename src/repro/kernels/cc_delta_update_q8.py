"""Quantized (int8) Pallas kernel for the fused CC-FedAvg round update.

Same round semantics as :mod:`repro.kernels.cc_delta_update`, but the
O(N·P) client Δ history lives in int8 with one f32 scale per client row
(symmetric quantization, q = clip(round(x/scale), ±127), matching
:func:`repro.core.compress.quantize_tree`). One VMEM pass per tile:

    deq_i   = payload_i · scale_i                      (dequantize)
    est_i   = e_replay_i·deq_i + e_stale_i·stale_i     (strategy estimate)
    d_i     = train_i ? (x_K^i − x_t) : est_i
    x_{t+1} = x_t + (Σ agg_w_i·d_i / denom) · post_scale
    q'_i    = upd_i ? clip(round((x_K^i − x_t)·inv_scale'_i)) : payload_i

The new per-row scales are computed *outside* the kernel in O(N) row
maxima: updating rows requantize against max|x_K^i − x_t|, rows that keep
their history only have their scale multiplied by the strategy's
store_scale — the int8 payload is copied through unchanged, so a skipping
client's decay (cc_decay's γ) costs no extra quantization error.

Payoff: the history gather/scatter and the aggregation pass move 4× fewer
bytes, and replay-style strategies (needs_stale=False — every strategy
except s2/ccc) never read the (N, P) f32 prev_local at all, so the carry
drops it entirely.

On CPU the public wrapper (:func:`repro.kernels.ops.cc_delta_update_q8`)
dispatches to :func:`cc_delta_update_q8_jnp`, a vectorized XLA path with
bit-identical payload/scale outputs (only the f32 summation order of the
global update differs); the Pallas path is pinned bit-exact against the
sequential reference in :func:`repro.kernels.ref.cc_delta_update_q8_ref`
in interpret mode, and its Mosaic lowering for v5e is compiled by
``tests/test_tpu_compile.py``. Blocks are VMEM-sized exactly as in the f32
kernel, with the int8 payload tiles counted at 32 rows per sublane tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cc_delta_update import _block_and_pad, _pad_cols

_QMAX = 127.0

#: chunk length for the accumulator-style row maxima, and the column count
#: above which it replaces the plain ``jnp.max``. XLA:CPU lowers a plain
#: axis-1 reduce to a scalar loop (~1.5 GB/s on one core); an explicit
#: elementwise ``maximum`` accumulator over (1, chunk) slices vectorizes
#: (~2×), and rows with upd=0 skip the pass entirely — their maxima are
#: discarded by the ``where`` anyway. max is exactly associative and
#: commutative, so every accumulation order gives bit-identical scales.
_MX_CHUNK = 16384
_MX_MIN_COLS = 2 * _MX_CHUNK


def _row_maxima(locals_, globals_, upd):
    """Per-row max|locals − globals|, exactly equal to
    ``jnp.max(|x − g|, axis=1)`` on every row with upd > 0 (rows with
    upd = 0 may return a partial maximum — callers mask them out)."""
    x = locals_.astype(jnp.float32)
    g = globals_.astype(jnp.float32)
    n, p = x.shape
    if p < _MX_MIN_COLS:
        return jnp.max(jnp.abs(x - g[None]), axis=1)
    c = p // _MX_CHUNK
    tail = p - c * _MX_CHUNK
    tail_mx = (jnp.max(jnp.abs(x[:, c * _MX_CHUNK:]
                               - g[None, c * _MX_CHUNK:]), axis=1)
               if tail else jnp.zeros((n,), jnp.float32))

    def row_body(i, acc):
        def compute(_):
            def chunk_body(j, a):
                xc = lax.dynamic_slice(x, (i, j * _MX_CHUNK),
                                       (1, _MX_CHUNK))[0]
                gc = lax.dynamic_slice(g, (j * _MX_CHUNK,), (_MX_CHUNK,))
                return jnp.maximum(a, jnp.abs(xc - gc))
            part = lax.fori_loop(0, c, chunk_body,
                                 jnp.zeros((_MX_CHUNK,), jnp.float32))
            return jnp.max(part)
        m = lax.cond(upd[i] > 0, compute, lambda _: jnp.float32(0.0), None)
        return acc.at[i].set(m)

    mx = lax.fori_loop(0, n, row_body, jnp.zeros((n,), jnp.float32))
    return jnp.maximum(mx, tail_mx)


def q8_new_scales(locals_, globals_, scales, upd, store_scale):
    """New per-row scales + inverse, computed outside the kernel in O(N·P)
    row maxima (one read pass over updating rows' locals)."""
    trained_mx = _row_maxima(locals_, globals_, upd)
    updated = jnp.maximum(trained_mx, 1e-12) / _QMAX
    kept = scales * store_scale.astype(jnp.float32)
    new_scales = jnp.where(upd > 0, updated, kept)
    inv = jnp.where(upd > 0, 1.0 / jnp.maximum(new_scales, 1e-30), 0.0)
    return new_scales, inv


def _cc_q8_kernel(rows_ref, extras_ref, locals_ref, payload_ref, *rest,
                  n_clients: int, has_stale: bool):
    if has_stale:
        stale_ref, global_ref, new_payload_ref, new_global_ref = rest
    else:
        global_ref, new_payload_ref, new_global_ref = rest
    g = global_ref[...].astype(jnp.float32)          # (1, block)
    acc = jnp.zeros_like(g)
    for i in range(n_clients):                        # N is small & static
        train_i = rows_ref[0, i]
        upd_i = rows_ref[1, i]
        w_i = rows_ref[2, i]
        q = payload_ref[i].astype(jnp.float32)
        deq = q * rows_ref[5, i]                      # old scale
        trained = locals_ref[i].astype(jnp.float32) - g[0]
        est = rows_ref[3, i] * deq
        if has_stale:
            est = est + rows_ref[4, i] * stale_ref[i].astype(jnp.float32)
        d_i = jnp.where(train_i > 0, trained, est)
        newq = jnp.clip(jnp.round(trained * rows_ref[6, i]), -_QMAX, _QMAX)
        new_payload_ref[i, :] = jnp.where(upd_i > 0, newq, q
                                          ).astype(jnp.int8)
        acc = acc + w_i * d_i[None]
    new_global_ref[...] = (
        g + (acc / extras_ref[0]) * extras_ref[1]
    ).astype(new_global_ref.dtype)


def cc_delta_update_q8_fwd(locals_, payload, scales, globals_, train, upd,
                           agg_w, e_replay, e_stale, store_scale, denom,
                           post_scale, stale=None, *,
                           block: int | None = None,
                           interpret: bool = False):
    """Fused int8 round update (Pallas path).

    locals_: (N, P) f32; payload: (N, P) int8; scales: (N,) f32 per-row
    quantization scales; globals_: (P,); coefficient rows: (N,); denom /
    post_scale: scalars. Returns (new_payload (N, P) int8, new_scales (N,),
    new_global (P,)).
    """
    n, p = locals_.shape
    has_stale = stale is not None
    mats = [locals_.dtype, jnp.int8, jnp.int8]
    if has_stale:
        mats.append(stale.dtype)
    block, p_pad = _block_and_pad(p, n, mats, block)
    updf = upd.astype(jnp.float32)
    new_scales, inv = q8_new_scales(locals_, globals_, scales, updf,
                                    store_scale)
    rows = jnp.stack([train.astype(jnp.float32), updf,
                      agg_w.astype(jnp.float32),
                      e_replay.astype(jnp.float32),
                      e_stale.astype(jnp.float32),
                      scales.astype(jnp.float32), inv])
    extras = jnp.stack([jnp.asarray(denom, jnp.float32),
                        jnp.asarray(post_scale, jnp.float32)])
    kernel = functools.partial(_cc_q8_kernel, n_clients=n,
                               has_stale=has_stale)
    mat_spec = pl.BlockSpec((n, block), lambda ip, rows, extras: (0, ip))
    vec_spec = pl.BlockSpec((1, block), lambda ip, rows, extras: (0, ip))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(p_pad // block,),
        in_specs=[mat_spec, mat_spec] + ([mat_spec] if has_stale else [])
        + [vec_spec],
        out_specs=[mat_spec, vec_spec],
    )
    operands = [_pad_cols(locals_, p_pad), _pad_cols(payload, p_pad)]
    if has_stale:
        operands.append(_pad_cols(stale, p_pad))
    operands.append(_pad_cols(globals_.reshape(1, -1), p_pad))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, p_pad), jnp.int8),
            jax.ShapeDtypeStruct((1, p_pad), globals_.dtype),
        ],
        interpret=interpret,
    )(rows, extras, *operands)
    return out[0][:, :p], new_scales, out[1].reshape(-1)[:p]


def _weighted_int8_rowsum(payload, w):
    """Σ_i w_i · payload_i as f32 without materializing the (N, P) f32
    cast: per-row axpy with zero-weight rows (every training client)
    skipped. Sum order differs from the vectorized formula — callers only
    use this on the allclose-pinned global, never on payload/scales."""
    n, p = payload.shape

    def body(i, acc):
        def add(a):
            row = lax.dynamic_slice(payload, (i, 0), (1, p))[0]
            return a + w[i] * row.astype(jnp.float32)
        return lax.cond(w[i] != 0, add, lambda a: a, acc)

    return lax.fori_loop(0, n, body, jnp.zeros((p,), jnp.float32))


def cc_delta_update_q8_jnp(locals_, payload, scales, globals_, train, upd,
                           agg_w, e_replay, e_stale, store_scale, denom,
                           post_scale, stale=None):
    """Vectorized XLA path (the CPU implementation of the same op).

    Payload and scale outputs are bit-identical to the Pallas path — the
    elementwise dequant/requant math is the same; only the f32 summation
    order of the aggregated global differs. The aggregation is decomposed
    into matvecs (Σw·(x−g) = w@x − Σw·g etc.): XLA:CPU's reduce loops run
    far below memory bandwidth on the (N, P) masked sum, while gemv and
    the elementwise requant pass stream near the roofline.
    """
    g = globals_.astype(jnp.float32)
    updf = upd.astype(jnp.float32)
    new_scales, inv = q8_new_scales(locals_, globals_, scales, updf,
                                    store_scale)
    trained = locals_.astype(jnp.float32) - g[None]
    tmask = (train > 0).astype(jnp.float32)
    aw = agg_w.astype(jnp.float32)
    wt = aw * tmask                                   # trained-delta rows
    wq = aw * (1.0 - tmask) * e_replay.astype(jnp.float32) * scales
    agg = (wt @ locals_.astype(jnp.float32) - jnp.sum(wt) * g
           + _weighted_int8_rowsum(payload, wq))
    if stale is not None:
        ws = aw * (1.0 - tmask) * e_stale.astype(jnp.float32)
        agg = agg + ws @ stale.astype(jnp.float32)
    new_global = (g + (agg / denom) * post_scale).astype(globals_.dtype)
    newq = jnp.clip(jnp.round(trained * inv[:, None]), -_QMAX, _QMAX)
    new_payload = jnp.where(updf[:, None] > 0, newq,
                            payload.astype(jnp.float32)).astype(jnp.int8)
    return new_payload, new_scales, new_global
