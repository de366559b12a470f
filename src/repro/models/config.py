"""Architecture configuration schema.

An :class:`ArchConfig` fully describes one decoder-only model in the zoo.
Layers are organized into *segments*: a segment is a super-block pattern of
block types repeated ``repeat`` times. Uniform models are one segment with a
single-type pattern (ideal ``lax.scan``); Griffin-style hybrids repeat a
(rec, rec, attn) super-block; tiny mixed models may use repeat=1 segments
(Python loop). This keeps every lowered HLO O(one super-block) while keeping
per-layer FLOPs exact (no lax.switch branch padding).

Block types
-----------
``attn``   global causal attention (GQA, RoPE, optional QK-norm)
``swa``    sliding-window causal attention
``mla``    multi-head latent attention (DeepSeek/MiniCPM3 style)
``mrope``  global attention with multimodal RoPE sections (Qwen2-VL)
``rglru``  Griffin RG-LRU recurrent block (temporal conv + gated LRU)
``slstm``  xLSTM scalar-memory LSTM block
``mlstm``  xLSTM matrix-memory LSTM block

FFN kinds: ``swiglu`` | ``moe`` | ``none`` (x-LSTM blocks carry their own
up/down projections). An MoE model may lead with ``n_dense_layers`` layers
whose FFN is a dense SwiGLU of width ``d_ff`` (DeepSeek-V3's
``first_k_dense_replace``); they fill whole leading segments, so that no
scanned segment mixes the two FFN shapes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

ATTENTION_KINDS = ("attn", "swa", "mla", "mrope")
RECURRENT_KINDS = ("rglru", "slstm", "mlstm")
BLOCK_KINDS = ATTENTION_KINDS + RECURRENT_KINDS


@dataclass(frozen=True)
class Segment:
    repeat: int
    pattern: tuple[str, ...]

    def __post_init__(self):
        for b in self.pattern:
            if b not in BLOCK_KINDS:
                raise ValueError(f"unknown block kind {b!r}")

    @property
    def n_layers(self) -> int:
        return self.repeat * len(self.pattern)


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    group_size: int = 256          # GShard dispatch group size (tokens)
    aux_loss_coef: float = 0.01
    router_dtype: str = "float32"
    expert_parallel: bool = False  # False: TP on d_ff; True: EP + all-to-all
    # routing: "softmax" top-k (OLMoE, Mixtral), or DeepSeek-V3's
    # ``noaux_tc``: "sigmoid" scores whose top-k is chosen on score + a
    # per-expert correction bias that only selects, the chosen scores
    # normalized and scaled by ``routed_scale`` (routed_scaling_factor)
    scoring: str = "softmax"
    routed_scale: float = 1.0
    # dropless: sort the token choices by expert and run grouped matmuls
    # over every choice; capacity_factor and group_size are then unused
    dropless: bool = False

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown MoE scoring {self.scoring!r}")


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int | None         # None: q_proj maps d to the heads
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    absorb: bool = True  # weight-absorbed decode (latent-space attention)


@dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                      # dense|moe|audio|vlm|hybrid|ssm
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    segments: tuple[Segment, ...]
    ffn_kind: str = "swiglu"            # swiglu | moe | none
    n_dense_layers: int = 0             # leading dense-FFN layers of an MoE
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    # attention details
    qk_norm: bool = False
    sliding_window: int = 0             # window for 'swa' blocks
    rope_theta: float = 10000.0
    mrope_sections: tuple[int, ...] = ()
    # recurrent details
    rg_conv_width: int = 4
    rg_d_rnn: int = 0                   # 0 -> d_model
    # embeddings / heads
    n_codebooks: int = 0                # musicgen: EnCodec codebook streams
    n_vision_tokens: int = 0            # qwen2-vl: stub patch-embed prefix
    tie_embeddings: bool = True
    # numerics
    norm_eps: float = 1e-6              # every RMSNorm of the stack
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # KV-cache storage dtype for serving. fp8 halves decode-cache HBM —
    # needed for the MHA archs whose 32k×128 caches exceed v5e HBM
    # (beyond-paper optimization; §Perf).
    kv_cache_dtype: str = "bfloat16"
    # serve-path Pallas kernels (flash attention / RG-LRU scan). Forward
    # only (no custom VJP), so training always uses the jnp path; on
    # non-TPU backends the kernels run through the Pallas interpreter.
    use_pallas: bool = False
    # long-context serving: if >0, serve_step for the long_500k shape uses
    # this sliding window (sub-quadratic carve-out for full-attention archs;
    # recorded as a deviation in DESIGN.md §4).
    long_context_window: int = 0
    # loss / memory knobs
    loss_chunk: int = 512
    attn_q_chunk: int = 512
    attn_k_chunk: int = 1024
    remat: bool = True
    citation: str = ""

    def __post_init__(self):
        if self.ffn_kind == "moe" and self.moe is None:
            raise ValueError(f"{self.name}: ffn_kind=moe requires MoEConfig")
        if any("mla" in s.pattern for s in self.segments) and self.mla is None:
            raise ValueError(f"{self.name}: mla blocks require MLAConfig")
        if self.n_heads % max(1, self.n_kv_heads):
            raise ValueError(f"{self.name}: n_heads must divide by n_kv_heads")
        if self.n_dense_layers:
            if self.ffn_kind != "moe":
                raise ValueError(f"{self.name}: n_dense_layers needs "
                                 "ffn_kind=moe")
            firsts = [0]
            for s in self.segments:
                firsts.append(firsts[-1] + s.n_layers)
            if self.n_dense_layers not in firsts[1:-1]:
                raise ValueError(
                    f"{self.name}: the {self.n_dense_layers} dense layers "
                    "must fill whole leading segments, before an MoE one")

    def ffn_of(self, layer: int) -> str:
        """The FFN kind of layer ``layer`` (0-based)."""
        return "swiglu" if layer < self.n_dense_layers else self.ffn_kind

    @property
    def n_layers(self) -> int:
        return sum(s.n_layers for s in self.segments)

    @property
    def d_rnn(self) -> int:
        return self.rg_d_rnn or self.d_model

    def block_kinds(self) -> tuple[str, ...]:
        out: list[str] = []
        for s in self.segments:
            for b in s.pattern:
                if b not in out:
                    out.append(b)
        return tuple(out)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: ≤2 layers-worth of segments, d_model≤256,
        ≤4 experts — runs a real fwd/train step on CPU."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        ratio = max(1, self.n_heads // max(1, self.n_kv_heads))
        n_kv = max(1, n_heads // ratio)
        head_dim = min(self.head_dim, 32)
        # 2 layers keeping one of each distinct kind from the original;
        # a leading dense layer keeps one, before one MoE layer
        kinds = self.block_kinds()
        if self.n_dense_layers:
            segs = (Segment(1, (kinds[0],)), Segment(1, (kinds[-1],)))
        elif len(kinds) > 1:
            segs = (Segment(repeat=1, pattern=kinds[:2]),)
        else:
            segs = (Segment(repeat=2, pattern=(kinds[0],)),)
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe, n_experts=4, top_k=min(2, self.moe.top_k),
                d_ff_expert=64, n_shared_experts=min(1, self.moe.n_shared_experts),
                group_size=16)
        mla = None
        if self.mla is not None:
            mla = MLAConfig(q_lora_rank=None if self.mla.q_lora_rank is None
                            else 48, kv_lora_rank=32, qk_nope_dim=16,
                            qk_rope_dim=16, v_head_dim=16,
                            absorb=self.mla.absorb)
            head_dim = 32
        mrope = self.mrope_sections
        if mrope:
            half = head_dim // 2
            q = half // 4
            mrope = (half - 2 * q, q, q)
        return self.replace(
            d_model=d_model, n_heads=n_heads, n_kv_heads=n_kv,
            head_dim=head_dim, d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            mrope_sections=mrope,
            vocab=min(self.vocab, 512), segments=segs, moe=moe, mla=mla,
            n_dense_layers=min(self.n_dense_layers, 1),
            sliding_window=min(self.sliding_window, 8) if self.sliding_window else 0,
            long_context_window=min(self.long_context_window, 8)
            if self.long_context_window else 0,
            rg_d_rnn=min(self.d_rnn, 256) if self.rg_d_rnn else 0,
            n_vision_tokens=min(self.n_vision_tokens, 4),
            kv_cache_dtype="bfloat16",   # fp8 is a full-scale-serving knob
            loss_chunk=16, attn_q_chunk=8, attn_k_chunk=8)


@dataclass(frozen=True)
class InputShape:
    """One of the four assigned workload shapes."""
    name: str
    seq_len: int
    global_batch: int
    mode: str  # 'train' | 'prefill' | 'decode'


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
