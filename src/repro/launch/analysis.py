"""Roofline-term extraction from compiled XLA artifacts.

``cost_analysis()`` on a GSPMD-compiled executable reports the *per-device*
program (FLOPs and bytes on the sharded shapes), so all three roofline
terms below are per-chip seconds; with even sharding they equal the
prompt's ``global / (chips × peak)`` formulation.

``collective_bytes`` is not in ``cost_analysis()`` — we parse the compiled
(post-SPMD-partitioning) HLO text and sum the *output* tensor bytes of
every ``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all``
/ ``collective-permute`` op. Output bytes ≥ operand bytes for all-gather
(the worst direction on the wire) and equal them for the others, so this is
a link-traffic upper bound per hop.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from repro.launch.mesh import V5E, chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

# one tensor literal: f32[2048,16]{1,0}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# an HLO instruction line:  %name = <shape(s)> opcode(
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_by_op(hlo_text: str) -> dict[str, int]:
    """Sum output bytes of every collective op in the (per-device) HLO."""
    out: dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        shapes, opcode = m.groups()
        # normalize fused/start variants: all-reduce-start, all-gather-done…
        for op in COLLECTIVE_OPS:
            if opcode == op or opcode.startswith(op + "-start") \
                    or opcode == op + ".1":
                out[op] += _shape_bytes(shapes)
                break
    return out


@dataclass
class RooflineTerms:
    """Per-chip roofline seconds for one compiled step on a ``device_kind``
    chip (its peaks come from :func:`repro.launch.mesh.chip_peaks`)."""
    flops: float                  # per-device HLO FLOPs (loop-aware)
    hbm_bytes: float              # per-device bytes accessed (loop-aware)
    collective_bytes: float       # per-device collective output bytes
    by_op: dict = field(default_factory=dict)
    raw_flops: float = 0.0        # XLA cost_analysis (loop bodies ×1)
    raw_bytes: float = 0.0
    device_kind: str = V5E

    def __post_init__(self):
        chip_peaks(self.device_kind)       # an unknown chip raises here

    @property
    def compute_s(self) -> float:
        return self.flops / chip_peaks(self.device_kind)["flops_bf16"]

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / chip_peaks(self.device_kind)["hbm_bw"]

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / chip_peaks(self.device_kind)["ici_bw"]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def to_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "by_op": self.by_op,
            "raw_flops": self.raw_flops,
            "raw_bytes": self.raw_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
        }


def roofline_from_compiled(compiled,
                           device_kind: str = V5E) -> RooflineTerms:
    """Loop-aware terms from the compiled HLO (see :mod:`.hlo_cost` — XLA's
    own cost_analysis counts while bodies once, which undercounts
    scan-over-layers programs by ~n_layers), priced at ``device_kind``'s
    peaks (default: the v5e the production meshes model). Raw XLA numbers
    are kept in ``raw_*`` for reference."""
    from repro.launch.hlo_cost import loop_aware_costs

    cost = compiled.cost_analysis()
    if isinstance(cost, list):      # older jax returns [dict]
        cost = cost[0]
    totals = loop_aware_costs(compiled.as_text())
    terms = RooflineTerms(
        flops=totals.flops, hbm_bytes=totals.bytes,
        collective_bytes=totals.collective_bytes,
        by_op=dict(totals.collective_by_op), device_kind=device_kind)
    terms.raw_flops = float(cost.get("flops", 0.0))
    terms.raw_bytes = float(cost.get("bytes accessed", 0.0))
    return terms


def model_flops_train(cfg, n_tokens: int) -> float:
    """6·N·D with N = active params (MoE: top-k + shared experts only)."""
    n_active = active_param_count(cfg)
    return 6.0 * n_active * n_tokens


def model_flops_decode(cfg, n_tokens: int) -> float:
    return 2.0 * active_param_count(cfg) * n_tokens


def total_param_count(cfg) -> int:
    """All stored parameters (MoE counts every expert) — the storage-side
    count the weight-stationary decode decision needs."""
    total = active_param_count(cfg)
    if cfg.moe is not None:
        m = cfg.moe
        inactive = m.n_experts - m.top_k
        total += ((cfg.n_layers - cfg.n_dense_layers) * inactive * 3
                  * cfg.d_model * m.d_ff_expert)
    return int(total)


def active_param_count(cfg) -> int:
    """Analytic parameter count; MoE counts top_k (+shared) experts."""
    d, v = cfg.d_model, cfg.vocab
    total = v * d                                  # embedding
    if not cfg.tie_embeddings:
        total += d * v * max(1, cfg.n_codebooks or 1)
    if cfg.n_codebooks:
        total += (cfg.n_codebooks - 1) * v * d     # extra codebook tables
    first = 0
    for seg in cfg.segments:
        for kind in seg.pattern:
            total += seg.repeat * _block_params(cfg, kind,
                                                cfg.ffn_of(first))
        first += seg.n_layers
    return int(total)


def _block_params(cfg, kind: str, ffn: str) -> int:
    d = cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = 2 * d                                       # the two norms
    if kind in ("attn", "swa", "mrope"):
        p += d * h * hd + 2 * d * kv * hd + h * hd * d
    elif kind == "mla":
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        q = (d * h * qk if m.q_lora_rank is None
             else d * m.q_lora_rank + m.q_lora_rank * h * qk)
        p += (q + d * (m.kv_lora_rank + m.qk_rope_dim)
              + m.kv_lora_rank * h * (m.qk_nope_dim + m.v_head_dim)
              + h * m.v_head_dim * d)
    elif kind == "rglru":
        dr = cfg.d_rnn
        p += 2 * d * dr + 2 * dr * dr + dr * d + cfg.rg_conv_width * dr
    elif kind == "mlstm":
        di = 2 * d
        p += 2 * d * di + 3 * di * di + di * d + 4 * di
    elif kind == "slstm":
        p += d * 4 * d + 4 * (d // max(1, h)) * d + d * 2 * d + d * d
    # FFN half
    if kind in ("attn", "swa", "mrope", "mla", "rglru") and ffn != "none":
        if ffn == "moe":
            m = cfg.moe
            active_e = m.top_k + m.n_shared_experts
            p += active_e * 3 * d * m.d_ff_expert + d * m.n_experts
        else:
            p += 3 * d * cfg.d_ff
    return p
