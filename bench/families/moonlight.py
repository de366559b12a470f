"""Moonlight-16B-A3B fine-tuned by federated LoRA: the ``moonlight`` family.

The configuration file holds the published ``config.json`` of
hf:moonshotai/Moonlight-16B-A3B key for key at its top level; ``layers``
says how many of its layers this chip holds (layer 0, dense, and the MoE
layers after it: one stage of a pipeline), and ``model`` the LoRA
adapters, the sequence length and the base's seed.

* Data: token sequences made on the device in one jitted call. Each of
  ``topics`` topics has a random template of S+1 tokens over the
  vocabulary; a sequence is its topic's template with each token replaced,
  at probability ``token_noise``, by a uniform one. Client ``i`` of ``N``
  draws ``gamma`` of its topics uniformly and the rest from its own block
  of ``topics / N`` (as ``resnet18gn`` draws classes). An example's
  features ``x`` are its topic and its label ``y`` its S+1 tokens: the
  model reads ``y``, and a bfloat16 copy of the features (the reference's
  control) leaves the tokens whole.
* The frozen base: every matrix N(0, ``init_std``²), every norm 1, the
  router's correction bias N(0, ``bias_std``²), each (weight, layer,
  expert) from its own key of ``base_seed``, in bfloat16. The program's
  base is made once on the device (:func:`base_params`); the reference
  makes each block when it reaches it, from the same keys, so both read
  the same numbers without the reference holding the base.
* Weights (the trained tree): LoRA factors of the program's layout, A
  normal with std d_in^-1/2 and B zero, float32.
* The program's model: ``repro.models.zoo.make_lm`` over the registry's
  ``moonshot-v1-16b-a3b``, every width from this file's published keys,
  cut to ``layers``.
* The loss: ``bench/reference/moonlight.py``.
* FLOPs: the multiply-accumulates of every product, times two. A token's
  attention reads its causal keys, (S + 1) / 2 of them on average; the
  MoE layer counts the chosen experts only. A training step adds the
  activation gradients (one product per forward product, two per
  attention product) and the LoRA factors' gradients (two per forward
  LoRA product), and no weight gradient of the frozen base.
  :func:`moe_gmm_work` counts the grouped matmuls the program runs in a
  training step, rematerialization included, and their bytes.
"""
from __future__ import annotations

import copy
import functools
import json
import math
import zlib

import jax
import jax.numpy as jnp

from bench.reference import moonlight as ref

#: the published config's keys whose values the program's model is built
#: from, and the ones that must hold as they do for it to be Moonlight
FIXED = {"model_type": "deepseek_v3", "hidden_act": "silu",
         "attention_bias": False, "q_lora_rank": None, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
         "norm_topk_prob": True, "moe_layer_freq": 1,
         "num_nextn_predict_layers": 0, "first_k_dense_replace": 1,
         "tie_word_embeddings": False}
#: the attention's projections: q_proj, kv_a_proj_with_mqa, the key and
#: value halves of kv_b_proj, o_proj
PROJECTIONS = ("wq", "wkv_a", "wk_b", "wv_b", "wo")
EXPERT = ("w_gate", "w_up", "w_down")


def _check(config: dict) -> None:
    for k, v in FIXED.items():
        if config[k] != v:
            raise ValueError(f"{config['name']}: {k} is {config[k]!r}, the "
                             f"moonlight family runs {v!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("MLA has as many key heads as query heads")


def _dims(config: dict) -> ref.Dims:
    m = config["model"]
    return ref.Dims(
        d=config["hidden_size"], heads=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
        experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        scale=config["routed_scaling_factor"], layers=config["layers"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        lora_scale=m["lora_alpha"] / m["lora_rank"])


# ---- the frozen base -------------------------------------------------------


def _shapes(config: dict) -> tuple[dict, dict, dict]:
    """(per-layer weights, per-expert weights, top-level weights): name →
    shape of one layer's, one expert's, or the single weight."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    c, e = config["kv_lora_rank"], config["n_routed_experts"]
    f, fs = (config["moe_intermediate_size"],
             config["moe_intermediate_size"] * config["n_shared_experts"])
    fd, vocab = config["intermediate_size"], config["vocab_size"]
    layer = {"norm1/scale": (d,), "norm2/scale": (d,),
             "mixer/wq": (d, h * (nope + rope)),
             "mixer/wkv_a": (d, c + rope), "mixer/kv_norm/scale": (c,),
             "mixer/wk_b": (c, h * nope), "mixer/wv_b": (c, h * v),
             "mixer/wo": (h * v, d)}
    dense = {"ffn/w_gate": (d, fd), "ffn/w_up": (d, fd), "ffn/w_down": (fd, d)}
    moe = {"ffn/router": (d, e), "ffn/score_bias": (e,),
           "ffn/shared/w_gate": (d, fs), "ffn/shared/w_up": (d, fs),
           "ffn/shared/w_down": (fs, d)}
    expert = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    top = {"embed/table": (vocab, d), "final_norm/scale": (d,),
           "lm_head": (d, vocab)}
    return {0: {**layer, **dense}, 1: {**layer, **moe}}, expert, top


def _make(config: dict, name: str, layer: int, expert, shape):
    """One weight of the base in bfloat16, from its own key: norms 1, the
    correction bias N(0, bias_std²), every other N(0, init_std²).
    ``expert`` may be traced."""
    m = config["model"]
    if name.endswith("/scale"):
        return jnp.ones(shape, jnp.bfloat16)
    key = jax.random.fold_in(jax.random.PRNGKey(m["base_seed"]),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(jax.random.fold_in(key, layer + 1), expert + 1)
    std = m["bias_std"] if name.endswith("score_bias") else m["init_std"]
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16)


def _layer(config: dict, l) -> dict:
    """Layer ``l``'s weights but its routed experts: layer 0 (a Python
    int) dense, any other (which may be traced) MoE."""
    per_layer, _, _ = _shapes(config)
    dense = isinstance(l, int) and l == 0
    return {n: _make(config, n, l, -1, s)
            for n, s in per_layer[0 if dense else 1].items()}


def _experts(config: dict, l: int, name: str, first, count: int):
    _, expert, _ = _shapes(config)
    ids = first + jnp.arange(count)
    return jax.vmap(lambda e: _make(config, f"ffn/{name}", l, e,
                                    expert[name]))(ids)


def _top(config: dict) -> dict:
    _, _, top = _shapes(config)
    return {n: _make(config, n, -1, -1, s) for n, s in top.items()}


def weights(config: dict) -> ref.Weights:
    """The base as the reference reads it: each block made where used."""
    return ref.Weights(layer=functools.partial(_layer, config),
                       experts=functools.partial(_experts, config),
                       top=functools.partial(_top, config))


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def base_params(config: dict):
    """The frozen base on the device, in the program's layout
    (``repro.models.decoder.model_init``): layer 0, then the MoE layers
    stacked. One jitted call makes a layer's weights, one each routed
    expert weight of every MoE layer (a layer at a time), and one the
    embedding, final norm and head."""
    moe_layers = jnp.arange(1, config["layers"])
    n_experts = config["n_routed_experts"]
    layer = jax.jit(functools.partial(_layer, config), static_argnums=0)

    @functools.partial(jax.jit, static_argnums=0)
    def experts(name):
        return jax.lax.map(
            lambda l: _experts(config, l, name, 0, n_experts), moe_layers)

    dense = _nest(layer(0))
    moe = [layer(l) for l in range(1, config["layers"])]
    moe = _nest({n: jnp.stack([w[n] for w in moe]) for n in moe[0]})
    for name in EXPERT:
        moe["ffn"][name] = experts(name)
    top = _nest(jax.jit(functools.partial(_top, config))())
    return {**top, "segments": [[dense], [moe]]}


# ---- data, adapters, the program's model, the loss -------------------------


@functools.partial(jax.jit, static_argnames=(
    "n_clients", "n_local", "n_test", "seq", "vocab", "topics", "gamma",
    "noise"))
def _sequences(key, *, n_clients, n_local, n_test, seq, vocab, topics,
               gamma, noise):
    k_t, k_iid, k_sh, k_m, k_r, k_ty, k_tm, k_tr = jax.random.split(key, 8)
    tmpl = jax.random.randint(k_t, (topics, seq), 0, vocab, jnp.int32)
    n_iid = int(round(gamma * n_local))
    y_iid = jax.random.randint(k_iid, (n_clients, n_iid), 0, topics)
    lo = (jnp.arange(n_clients) * topics) // n_clients
    hi = ((jnp.arange(n_clients) + 1) * topics) // n_clients
    u = jax.random.uniform(k_sh, (n_clients, n_local - n_iid))
    t_sh = lo[:, None] + jnp.floor(u * (hi - lo)[:, None]).astype(jnp.int32)
    topic = jnp.concatenate([y_iid, t_sh], axis=1).astype(jnp.int32)
    t_test = jax.random.randint(k_ty, (n_test,), 0, topics, jnp.int32)

    def tokens(t, k_mask, k_tok):
        shape = t.shape + (seq,)
        return jnp.where(jax.random.bernoulli(k_mask, noise, shape),
                         jax.random.randint(k_tok, shape, 0, vocab,
                                            jnp.int32),
                         tmpl[t])

    sizes = jnp.full((n_clients,), n_local, jnp.int32)
    return (topic, tokens(topic, k_m, k_r), sizes, t_test,
            tokens(t_test, k_tm, k_tr))


def make_data(config: dict, words) -> tuple:
    """Each client's topics (x) and token sequences (y), and a test split,
    on the device, from the seed's data words (``words[0]``)."""
    fed, m = config["federation"], config["model"]
    return _sequences(jax.random.PRNGKey(words[0]),
                      n_clients=fed["n_clients"],
                      n_local=fed["samples_per_client"],
                      n_test=fed["test_samples"], seq=m["seq_len"] + 1,
                      vocab=config["vocab_size"], topics=m["topics"],
                      gamma=fed["gamma"], noise=m["token_noise"])


def _adapted_shapes(config: dict) -> dict:
    """The adapted weights' program paths and (d_in, d_out), by segment:
    ``segments/0/0`` is layer 0, ``segments/1/0`` the stacked MoE layers."""
    per_layer, _, _ = _shapes(config)
    return {f"segments/{g}/0/mixer/{n}": per_layer[g][f"mixer/{n}"]
            for g in (0, 1) for n in config["model"]["lora_targets"]}


def init_params(config: dict, key):
    """The adapters, float32, in the program's layout: A normal with std
    d_in^-1/2, B zero, so that the first model is the base."""
    r, n_moe = config["model"]["lora_rank"], config["layers"] - 1
    out = {}
    for i, (path, (d_in, d_out)) in enumerate(
            sorted(_adapted_shapes(config).items())):
        lead = () if path.startswith("segments/0/") else (n_moe,)
        out[path] = {
            "lora_a": jax.random.normal(jax.random.fold_in(key, i),
                                        lead + (d_in, r)) / math.sqrt(d_in),
            "lora_b": jnp.zeros(lead + (r, d_out))}
    return {"lora": out}


def arch(config: dict):
    """The program's ``ArchConfig``: the registry's ``arch``, every width
    from the configuration's published keys, cut to its ``layers``."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.config import Segment
    _check(config)
    base = get_config(config["model"]["arch"])
    mla = dataclasses.replace(
        base.mla, q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"])
    moe = dataclasses.replace(
        base.moe, n_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        d_ff_expert=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        routed_scale=config["routed_scaling_factor"])
    return base.replace(
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        n_dense_layers=config["first_k_dense_replace"],
        segments=(Segment(1, ("mla",)),
                  Segment(config["layers"] - 1, ("mla",))),
        mla=mla, moe=moe,
        compute_dtype=config["execution"]["compute_dtype"])


def build_model(config: dict):
    """The program's federated LoRA model over a freshly made base."""
    from repro.models.zoo import make_lm
    m = config["model"]
    return make_lm(arch(config), base_params(config), rank=m["lora_rank"],
                   alpha=m["lora_alpha"],
                   targets=tuple(m["lora_targets"]))


def reference_lora(params, config: dict) -> dict:
    """The reference's view of the program's adapters: each adapted
    weight's (A, B) stacked over all the layers held."""
    out = {}
    for n in config["model"]["lora_targets"]:
        ab = [params["lora"][f"segments/{g}/0/mixer/{n}"] for g in (0, 1)]
        out[f"mixer/{n}"] = tuple(jnp.concatenate([ab[0][k][None], ab[1][k]])
                                  for k in ("lora_a", "lora_b"))
    return out


@functools.lru_cache(maxsize=4)
def _reference_loss(config_json: str):
    config = json.loads(config_json)
    dims, w = _dims(config), weights(config)

    def loss(params, seqs):
        return ref.loss(w, reference_lora(params, config), seqs, dims)

    return loss


def loss(params, xb, yb, config: dict):
    """The plain reference's mean next-token cross-entropy of the
    sequences ``yb`` (``xb``, the topics, is not read)."""
    return _reference_loss(json.dumps(config, sort_keys=True))(params, yb)


# ---- FLOPs and bytes -------------------------------------------------------


def _macs_per_token(config: dict) -> dict:
    """Multiply-accumulates of one token's forward pass, by kind."""
    per_layer, expert, top = _shapes(config)
    s, layers = config["model"]["seq_len"], config["layers"]
    h = config["num_attention_heads"]
    r = config["model"]["lora_rank"]

    def mat(shape):
        return shape[0] * shape[1]

    proj = sum(mat(per_layer[0][f"mixer/{n}"]) for n in PROJECTIONS)
    scores = h * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                  + config["v_head_dim"]) * (s + 1) / 2
    lora = sum(r * sum(per_layer[0][f"mixer/{n}"])
               for n in config["model"]["lora_targets"])
    dense = sum(mat(per_layer[0][f"ffn/{n}"]) for n in EXPERT)
    moe = (config["num_experts_per_tok"] * sum(map(mat, expert.values()))
           + sum(mat(per_layer[1][f"ffn/shared/{n}"]) for n in EXPERT)
           + mat(per_layer[1]["ffn/router"]))
    return {"proj": layers * proj, "scores": layers * scores,
            "lora": layers * lora, "dense": dense,
            "moe": (layers - 1) * moe, "head": mat(top["lm_head"])}


def forward_flops(config: dict) -> int:
    """FLOPs of one sequence's forward pass (its S tokens)."""
    macs = _macs_per_token(config)
    return int(2 * sum(macs.values()) * config["model"]["seq_len"])


def train_flops(config: dict) -> int:
    """FLOPs one sequence costs in a training step: the forward pass, the
    activation gradients and the LoRA factors' gradients; no weight
    gradient of the frozen base, and nothing recomputed."""
    macs = _macs_per_token(config)
    backward = (macs["proj"] + macs["dense"] + macs["moe"] + macs["head"]
                + 2 * macs["scores"] + 2 * macs["lora"])
    return int(2 * (sum(macs.values()) + backward)
               * config["model"]["seq_len"])


#: grouped matmuls a MoE layer runs per training step: gate, up and down
#: in the forward pass, again when the layer is rematerialized for the
#: backward pass, and the three activation-gradient products
GMM_PER_LAYER_STEP = 9


def moe_gmm_work(config: dict) -> tuple[int, int]:
    """(FLOPs, bytes) of the expert grouped matmuls of one training step
    of one client. Each product has rows R = batch · S · top_k, and reads
    every expert's weight once (E · d · f bfloat16), its R rows of input
    and writes its R rows of output, at 2 bytes each."""
    tr = config["training"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = (tr["batch_size"] * config["model"]["seq_len"]
            * config["num_experts_per_tok"])
    n = GMM_PER_LAYER_STEP * (config["layers"] - 1)
    flops = n * 2 * rows * d * f
    nbytes = n * 2 * (config["n_routed_experts"] * d * f + rows * (d + f))
    return flops, nbytes


def shrink(config: dict) -> dict:
    """The configuration at a size the CPU test run holds: the published
    structure (MLA without q_lora, a dense layer then MoE layers scanned,
    sigmoid routing with a bias, shared experts, untied head) at small
    widths, 3 layers, 17-token sequences, float32; the federation, the
    strategy and the schedule unchanged."""
    c = copy.deepcopy(config)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
             moe_intermediate_size=32, n_routed_experts=8,
             num_experts_per_tok=3, vocab_size=512, layers=3)
    c["model"].update(seq_len=16, topics=10)
    c["federation"].update(samples_per_client=32, test_samples=8)
    c["execution"].update(compute_dtype="float32")
    return c
