"""Pallas TPU kernel for the fused CC-FedAvg server round update.

This is the paper's own hot spot made into one HBM pass. Per parameter
element, given the stacked client results, Algorithm 1 lines 12/15/20/21 do:

    Δ_t^i  = train_i ? (x_K^i − x_t) : Δ̂_t^i           (train or estimate)
    Δ_t    = (1/|S_t|) Σ_{i∈S_t} sel_i · Δ_t^i         (aggregate)
    x_{t+1} = x_t + Δ_t                                 (global update)

Done naively this reads/writes each model-sized array several times
(compute trained delta, select, mean, add). The kernel streams one tile of
every operand through VMEM and produces both outputs (new per-client deltas
+ new global params) in a single pass — the op is purely HBM-bandwidth
bound, so fewer passes is the whole game on TPU.

The kernel is parameterized by a per-strategy *epilogue*
(:class:`repro.core.strategies.FusedEpilogue`): every strategy's estimate
is affine in the stored Δ and the stale-model delta, so per-client f32
coefficient rows — computed outside in O(N) — specialize one kernel body
to the whole registry:

    est_i   = e_replay_i·Δ_{t−1}^i + e_stale_i·stale_i
    d_i     = train_i ? (x_K^i − x_t) : est_i
    Δ_t^i   = upd_i ? (x_K^i − x_t) : store_scale_i·Δ_{t−1}^i
    x_{t+1} = x_t + (Σ agg_w_i·d_i / denom) · post_scale

Shapes: locals_, deltas (and the optional stale): (N, P) — N clients,
P flat params; globals_: (P,); coefficient rows: (N,) f32 in SMEM
(scalar-prefetch). P is zero-padded up to a lane-aligned block multiple
and sliced back, so awkward (prime-ish) P never degrades the block size.

The block is sized to VMEM (:func:`_block_and_pad`): every grid step holds
one (N, block) tile of each client-stacked operand and output plus the
(1, block) global in and out, all double-buffered, and the sum has to stay
inside the chip's scoped-VMEM limit. N is unrolled in the kernel body, so
a large N shrinks the block; past :func:`max_clients` no block fits and
the call raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128

#: bytes of VMEM the pipelined tiles may take: TPU v5e's default scoped-VMEM
#: limit is 16 MiB, and the remaining 4 MiB are headroom for Mosaic's own
#: scratch (a kernel past the limit fails to compile with RESOURCE_EXHAUSTED)
VMEM_BUDGET = 12 * 2 ** 20


def _tile_rows(n: int, dtype) -> int:
    """Rows an (n, block) tile occupies in VMEM: 32-bit dtypes tile by 8
    sublanes, narrower ones pack more rows per sublane (32 for int8)."""
    sub = 8 * 4 // jnp.dtype(dtype).itemsize
    return -(-n // sub) * sub


def _lane_bytes(n: int, mats) -> int:
    """VMEM bytes per lane of one grid step: the (n, block) operands and
    outputs of dtypes ``mats`` plus the (1, block) f32 global in and out
    (each padded to a full 8-sublane tile), all double-buffered."""
    rows = sum(_tile_rows(n, d) * jnp.dtype(d).itemsize for d in mats)
    return 2 * (rows + 2 * _tile_rows(1, jnp.float32) * 4)


def max_clients(mats) -> int:
    """Largest N whose smallest (one-lane-tile) block fits the budget."""
    n = 0
    while _lane_bytes(n + 1, mats) * _LANE <= VMEM_BUDGET:
        n += 1
    return n


def _block_and_pad(p: int, n: int, mats,
                   block: int | None = None) -> tuple[int, int]:
    """The largest lane-aligned block whose double-buffered tiles fit
    :data:`VMEM_BUDGET` (capped at ``block`` when given, and at P rounded
    up to a lane), plus the padded P it evenly divides. ``mats`` lists the
    dtypes of the (n, P) operands and outputs."""
    fit = VMEM_BUDGET // (_lane_bytes(n, mats) * _LANE) * _LANE
    if fit < _LANE:
        raise ValueError(
            f"fused kernel: {n} clients do not fit one {_LANE}-lane block "
            f"in the {VMEM_BUDGET >> 20} MiB VMEM budget; the largest N "
            f"for these operands is {max_clients(mats)}")
    if block is not None:
        fit = min(fit, block - block % _LANE)
    p_lane = -(-p // _LANE) * _LANE
    block = max(_LANE, min(fit, p_lane))
    return block, -(-p // block) * block


def _pad_cols(x, p_pad: int):
    p = x.shape[-1]
    if p == p_pad:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, p_pad - p)]
    return jnp.pad(x, widths)


def _cc_kernel(rows_ref, extras_ref, locals_ref, deltas_ref, *rest,
               n_clients: int, has_stale: bool):
    if has_stale:
        stale_ref, global_ref, new_deltas_ref, new_global_ref = rest
    else:
        global_ref, new_deltas_ref, new_global_ref = rest
    g = global_ref[...].astype(jnp.float32)          # (1, block)
    acc = jnp.zeros_like(g)
    for i in range(n_clients):                        # N is small & static
        train_i = rows_ref[0, i]
        upd_i = rows_ref[1, i]
        w_i = rows_ref[2, i]
        trained = locals_ref[i].astype(jnp.float32) - g[0]
        d_old = deltas_ref[i].astype(jnp.float32)
        est = rows_ref[3, i] * d_old
        if has_stale:
            est = est + rows_ref[4, i] * stale_ref[i].astype(jnp.float32)
        d_i = jnp.where(train_i > 0, trained, est)
        new_deltas_ref[i, :] = jnp.where(
            upd_i > 0, trained, rows_ref[5, i] * d_old
        ).astype(new_deltas_ref.dtype)
        acc = acc + w_i * d_i[None]
    new_global_ref[...] = (
        g + (acc / extras_ref[0]) * extras_ref[1]
    ).astype(new_global_ref.dtype)


def cc_epilogue_update_fwd(locals_, deltas, globals_, train, upd, agg_w,
                           e_replay, e_stale, store_scale, denom, post_scale,
                           stale=None, *, block: int | None = None,
                           interpret: bool = False):
    """Strategy-parameterized fused round update.

    locals_, deltas (and stale, when given): (N, P); globals_: (P,);
    train/upd/agg_w/e_replay/e_stale/store_scale: (N,); denom/post_scale:
    scalars. Returns (new_deltas (N, P), new_global (P,)). ``block`` caps
    the VMEM-sized block (None: the largest that fits).
    """
    n, p = locals_.shape
    has_stale = stale is not None
    mats = [locals_.dtype, deltas.dtype, deltas.dtype]
    if has_stale:
        mats.append(stale.dtype)
    block, p_pad = _block_and_pad(p, n, mats, block)
    rows = jnp.stack([train.astype(jnp.float32), upd.astype(jnp.float32),
                      agg_w.astype(jnp.float32),
                      e_replay.astype(jnp.float32),
                      e_stale.astype(jnp.float32),
                      store_scale.astype(jnp.float32)])
    extras = jnp.stack([jnp.asarray(denom, jnp.float32),
                        jnp.asarray(post_scale, jnp.float32)])
    kernel = functools.partial(_cc_kernel, n_clients=n, has_stale=has_stale)
    mat_spec = pl.BlockSpec((n, block), lambda ip, rows, extras: (0, ip))
    vec_spec = pl.BlockSpec((1, block), lambda ip, rows, extras: (0, ip))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(p_pad // block,),
        in_specs=[mat_spec, mat_spec] + ([mat_spec] if has_stale else [])
        + [vec_spec],
        out_specs=[mat_spec, vec_spec],
    )
    operands = [_pad_cols(locals_, p_pad), _pad_cols(deltas, p_pad)]
    if has_stale:
        operands.append(_pad_cols(stale, p_pad))
    operands.append(_pad_cols(globals_.reshape(1, -1), p_pad))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, p_pad), deltas.dtype),
            jax.ShapeDtypeStruct((1, p_pad), globals_.dtype),
        ],
        interpret=interpret,
    )(rows, extras, *operands)
    return out[0][:, :p], out[1].reshape(-1)[:p]


def cc_delta_update_fwd(locals_, deltas, globals_, train_mask, sel_mask, *,
                        block: int | None = None, interpret: bool = False):
    """Legacy fused round update (bit-compatible specialization).

    locals_: (N, P) client post-training params; deltas: (N, P) stored Δ;
    globals_: (P,); masks: (N,). Returns (new_deltas (N, P), new_global (P,)).

    The identity epilogue reproduces the original kernel bit-for-bit:
    e_replay=1 and store_scale=1 multiply exactly, post_scale=1 multiplies
    exactly, and denom = 1e-9 + Σ sel matches the old sequential mask
    accumulation (0/1 sums are exact in f32; the 1e-9 rounds away
    identically once any client is selected).
    """
    n, _ = locals_.shape
    train = train_mask.astype(jnp.float32)
    sel = sel_mask.astype(jnp.float32)
    ones = jnp.ones((n,), jnp.float32)
    return cc_epilogue_update_fwd(
        locals_, deltas, globals_, train, train, sel, ones,
        jnp.zeros((n,), jnp.float32), ones, 1e-9 + jnp.sum(sel),
        jnp.ones((), jnp.float32), block=block, interpret=interpret)
