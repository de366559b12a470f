"""Moonlight-16B-A3B (moonshot-v1-16b-a3b) — DeepSeek-V3-style MoE with
latent attention [hf:moonshotai/Moonlight-16B-A3B, config.json;
Muon paper arXiv:2502.16982].

The published config (``model_type`` ``deepseek_v3``): 27 layers, hidden
2048, 16 heads, vocabulary 163,840 (untied), RMSNorm eps 1e-5. Attention
is MLA with ``q_lora_rank`` null (``q_proj`` maps 2048 → 16 × 192
directly), ``kv_lora_rank`` 512, nope/rope/v head dims 128/64/128, RoPE
θ = 50,000 without scaling. Layer 0 is dense (``first_k_dense_replace``
1, SwiGLU of width 11,264); layers 1–26 hold 64 routed experts of width
1,408 and 2 shared ones. Routing is ``noaux_tc``: sigmoid scores, the top
6 chosen on score + ``e_score_correction_bias``, weighted by their scores
without the bias, normalized (``norm_topk_prob``) and scaled by
``routed_scaling_factor`` 2.446; one group (``n_group`` = ``topk_group``
= 1), and no token is dropped.
"""
from repro.models.config import ArchConfig, MLAConfig, MoEConfig, Segment

CONFIG = ArchConfig(
    name="moonshot-v1-16b-a3b",
    arch_type="moe",
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=11264,
    vocab=163840,
    segments=(Segment(repeat=1, pattern=("mla",)),
              Segment(repeat=26, pattern=("mla",))),
    ffn_kind="moe",
    n_dense_layers=1,
    # expert-parallel: 64 fine-grained experts shard over the model axis
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  n_shared_experts=2, expert_parallel=True,
                  scoring="sigmoid", routed_scale=2.446, dropless=True),
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128,
                  qk_rope_dim=64, v_head_dim=128),
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    long_context_window=8192,
    citation="hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2502.16982",
)
