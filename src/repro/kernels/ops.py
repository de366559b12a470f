"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; the
kernel bodies then execute through the Pallas interpreter, which is how the
test suite validates them against :mod:`repro.kernels.ref`). On a TPU
backend the same calls compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import cc_delta_update as _cc
from repro.kernels import cc_delta_update_q8 as _q8
from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import slstm_scan as _sl


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles of the grouped matmul: rows in tiles of up to 512 (dividing
    ``m``), and each weight tile as wide as VMEM holds double-buffered at
    the expert widths (k or n up to 1,408 whole, else 1,024), so that the
    token rows are read once per output tile and each expert's weights
    once per row tile its tokens touch."""
    tm = 512
    while m % tm:
        tm //= 2
    return tm, (k if k <= 1408 else 1024), (n if n <= 1408 else 1024)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(lhs, rhs, group_sizes, *, interpret: bool | None = None):
    """``lhs[rows of group g] @ rhs[g]`` for each group, rows sorted by
    group: lhs (m, k), rhs (G, k, n), group_sizes (G,) int32 summing to m;
    out (m, n) in lhs's dtype, accumulated in float32. The Pallas grouped
    matmul (``jax.experimental.pallas.ops.tpu.megablox``); its backward
    runs the same kernel on the transposed weights for ``lhs`` and a
    transposed grouped matmul for ``rhs``, which XLA drops where nothing
    differentiates ``rhs`` (a frozen base)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    interpret = _default_interpret() if interpret is None else interpret
    return gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling,
               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """Flash attention over (B, H, S, hd) / (B, Kv, S, hd) tensors."""
    interpret = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rglru_scan(a, b, h0, *, chunk: int = 128, block_d: int = 128,
               interpret: bool | None = None):
    """Linear recurrence h_t = a_t·h_{t−1} + b_t over (B, S, D)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _rg.rglru_scan_fwd(a.astype(jnp.float32), b.astype(jnp.float32),
                              h0.astype(jnp.float32), chunk=chunk,
                              block_d=block_d, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def slstm_scan(wx, r, h0, c0, n0, m0, *, chunk: int = 256,
               interpret: bool | None = None):
    """VMEM-resident sLSTM recurrence over (B, S, 4D) projections."""
    interpret = _default_interpret() if interpret is None else interpret
    f32 = jnp.float32
    return _sl.slstm_scan_fwd(wx.astype(f32), r, h0.astype(f32),
                              c0.astype(f32), n0.astype(f32),
                              m0.astype(f32), chunk=chunk,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def cc_delta_update(locals_, deltas, globals_, train_mask, sel_mask, *,
                    block: int | None = None, interpret: bool | None = None):
    """Fused CC-FedAvg round update over flat (N, P) client params."""
    interpret = _default_interpret() if interpret is None else interpret
    return _cc.cc_delta_update_fwd(locals_, deltas, globals_, train_mask,
                                   sel_mask, block=block,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def cc_epilogue_update(locals_, deltas, globals_, train, upd, agg_w,
                       e_replay, e_stale, store_scale, denom, post_scale,
                       stale=None, *, block: int | None = None,
                       interpret: bool | None = None):
    """Strategy-parameterized fused round update (f32 history)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _cc.cc_epilogue_update_fwd(
        locals_, deltas, globals_, train, upd, agg_w, e_replay, e_stale,
        store_scale, denom, post_scale, stale, block=block,
        interpret=interpret)


@jax.jit
def q8_gather_rows(payload, scales, idx):
    """Gather + dequantize cohort rows of an int8 (N, P) history store.

    The sharded history store (:mod:`repro.core.history_store`) keeps the
    full federation's Δ rows quantized and materializes f32 only for the
    active cohort — this is its gather primitive, one fused XLA program
    (take → widen → scale) so the f32 intermediate never exceeds (M, P).
    """
    from repro.core.compress import dequantize_rows
    return dequantize_rows(jnp.take(payload, idx, axis=0),
                           jnp.take(scales, idx, axis=0))


@jax.jit
def q8_scatter_rows(payload, scales, idx, rows):
    """Quantize + scatter updated cohort rows back into the int8 store.

    Per-row symmetric quantization (:func:`repro.core.compress.
    quantize_rows` semantics) of the (M, P) f32 rows, written at ``idx``;
    rows outside the cohort keep their payload/scale bits verbatim, which
    is what makes a checkpoint resume of the store bit-identical.
    """
    from repro.core.compress import quantize_rows
    q_payload, q_scales = quantize_rows(rows)
    return payload.at[idx].set(q_payload), scales.at[idx].set(q_scales)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def cc_delta_update_q8(locals_, payload, scales, globals_, train, upd,
                       agg_w, e_replay, e_stale, store_scale, denom,
                       post_scale, stale=None, *, block: int | None = None,
                       interpret: bool | None = None):
    """Strategy-parameterized fused round update over int8 Δ history.

    ``interpret=True`` (the off-TPU default) runs the vectorized XLA
    implementation — on CPU the Pallas interpreter is pure overhead, and
    the int8 win comes from moving/storing 4× fewer bytes, which XLA's
    fused elementwise path already realizes. On TPU the Pallas kernel
    compiles to Mosaic. Payload/scale outputs are bit-identical either
    way; kernel tests pin the Pallas path directly."""
    interpret = _default_interpret() if interpret is None else interpret
    if interpret:
        return _q8.cc_delta_update_q8_jnp(
            locals_, payload, scales, globals_, train, upd, agg_w,
            e_replay, e_stale, store_scale, denom, post_scale, stale)
    return _q8.cc_delta_update_q8_fwd(
        locals_, payload, scales, globals_, train, upd, agg_w, e_replay,
        e_stale, store_scale, denom, post_scale, stale, block=block,
        interpret=False)
