"""A second model family, for the test that a configuration brings its own
model with files alone (``bench/tests/test_family_fixture.py``).

The program's model is a zoo decoder (``repro.models.zoo``) wrapped in
LoRA adapters (``repro.models.lora``): the trainable tree is the adapter
subtree, and the base weights are closed over. The base is made here,
from the configuration's ``base_seed``, so that the reference and the
program start from the same frozen weights without the reference taking
anything from the program.

The plain reference below follows the zoo decoder's published pieces:
features become tokens by ``floor(sigmoid(x) · vocab)``; a token
embedding; ``n_layers`` pre-norm blocks of RMSNorm (eps 1e-6), causal
multi-head attention with interleaved-pair RoPE (θ = 10⁴), and a SwiGLU
MLP; a final RMSNorm; the tied embedding as head at the last position,
its first ``n_classes`` columns as logits. Each adapted weight is
``W + A·B`` (LoRA scale 1). Examples are made at the centres of their
tokens' bins, so a bfloat16 copy of them keeps its tokens.
"""
from __future__ import annotations

import copy
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

THETA = 1e4
EPS = 1e-6
TARGETS = ("mixer/wq", "mixer/wk", "mixer/wv", "mixer/wo", "ffn/w_down",
           "ffn/w_gate", "ffn/w_up")
PREFIX = "segments/0/0/"


def _dims(config: dict) -> dict:
    m = config["model"]
    d = m["d_model"]
    return {"d": d, "h": m["n_heads"], "hd": m["head_dim"], "f": m["d_ff"],
            "v": max(m["vocab"], m["n_classes"]), "l": m["n_layers"],
            "r": m["lora_rank"], "s": m["seq_len"], "c": m["n_classes"]}


def _shapes(z: dict) -> dict:
    """The adapted weights' (d_in, d_out)."""
    d, hh, f = z["d"], z["h"] * z["hd"], z["f"]
    return {"mixer/wq": (d, hh), "mixer/wk": (d, hh), "mixer/wv": (d, hh),
            "mixer/wo": (hh, d), "ffn/w_gate": (d, f), "ffn/w_up": (d, f),
            "ffn/w_down": (f, d)}


@functools.lru_cache(maxsize=4)
def _base(config_json: str):
    """The frozen base weights, in the zoo decoder's tree layout."""
    z = _dims(json.loads(config_json))
    key = jax.random.PRNGKey(json.loads(config_json)["model"]["base_seed"])
    keys = iter(jax.random.split(key, 16))
    blk = {"norm1": {"scale": jnp.ones((z["l"], z["d"]))},
           "norm2": {"scale": jnp.ones((z["l"], z["d"]))},
           "mixer": {}, "ffn": {}}
    for path, (i, o) in _shapes(z).items():
        group, name = path.split("/")
        blk[group][name] = (jax.random.normal(next(keys), (z["l"], i, o))
                            / math.sqrt(i))
    return {"embed": {"table": 0.02 * jax.random.normal(
                next(keys), (z["v"], z["d"]))},
            "segments": [[blk]],
            "final_norm": {"scale": jnp.ones((z["d"],))}}


def base_params(config: dict):
    return _base(json.dumps(config, sort_keys=True))


@functools.partial(jax.jit, static_argnames=(
    "n_clients", "n_local", "n_test", "seq", "vocab", "n_classes", "gamma",
    "noise"))
def _sequences(key, *, n_clients, n_local, n_test, seq, vocab, n_classes,
               gamma, noise):
    k_t, k_iid, k_sh, k_m, k_r, k_ty, k_tm, k_tr = jax.random.split(key, 8)
    tmpl = jax.random.randint(k_t, (n_classes, seq), 0, vocab)
    n_iid = int(round(gamma * n_local))
    y_iid = jax.random.randint(k_iid, (n_clients, n_iid), 0, n_classes)
    lo = (jnp.arange(n_clients) * n_classes) // n_clients
    hi = ((jnp.arange(n_clients) + 1) * n_classes) // n_clients
    u = jax.random.uniform(k_sh, (n_clients, n_local - n_iid))
    y_sh = lo[:, None] + jnp.floor(u * (hi - lo)[:, None]).astype(jnp.int32)
    y = jnp.concatenate([y_iid, y_sh], axis=1).astype(jnp.int32)
    y_test = jax.random.randint(k_ty, (n_test,), 0, n_classes
                                ).astype(jnp.int32)

    def features(labels, k_mask, k_tok):
        shape = labels.shape + (seq,)
        tok = jnp.where(jax.random.bernoulli(k_mask, noise, shape),
                        jax.random.randint(k_tok, shape, 0, vocab),
                        tmpl[labels])
        p = (tok + 0.5) / vocab
        return jnp.log(p / (1 - p))              # the centre of tok's bin

    sizes = jnp.full((n_clients,), n_local, jnp.int32)
    return (features(y, k_m, k_r), y, sizes, features(y_test, k_tm, k_tr),
            y_test)


def make_data(config: dict, words) -> tuple:
    fed, z = config["federation"], _dims(config)
    return _sequences(jax.random.PRNGKey(words[0]),
                      n_clients=fed["n_clients"],
                      n_local=fed["samples_per_client"],
                      n_test=fed["test_samples"], seq=z["s"], vocab=z["v"],
                      n_classes=z["c"], gamma=fed["gamma"],
                      noise=fed["noise"])


def init_params(config: dict, key):
    """LoRA factors: A normal with std d_in^-1/2, B zero, so the first
    model is the base."""
    z = _dims(config)
    keys = jax.random.split(key, len(TARGETS))
    return {"lora": {PREFIX + p: {
        "lora_a": jax.random.normal(k, (z["l"], i, z["r"])) / math.sqrt(i),
        "lora_b": jnp.zeros((z["l"], z["r"], o))}
        for k, p in zip(keys, TARGETS) for i, o in [_shapes(z)[p]]}}


def build_model(config: dict):
    from repro.models.lora import lora_classifier
    from repro.models.simple import Classifier
    from repro.models.zoo import make_zoo_classifier
    m, z = config["model"], _dims(config)
    if (z["d"] % 8 or z["h"] != 2 or z["hd"] * 2 != z["d"]
            or z["f"] != 2 * z["d"]):
        raise ValueError("the zoo decoder has 2 heads of d/2 and d_ff 2d")
    zoo = make_zoo_classifier("decoder", input_shape=(z["s"],),
                              n_classes=z["c"], width=z["d"] // 8,
                              n_layers=z["l"], vocab=m["vocab"])
    base = base_params(config)
    frozen = Classifier(zoo.name, lambda rng: base, zoo.apply)
    return lora_classifier(frozen, jax.random.PRNGKey(0), z["r"])


# ---- the plain reference ---------------------------------------------------


def _mm(a, b):
    p = (lax.Precision.HIGHEST if a.dtype == jnp.float32
         else lax.Precision.DEFAULT)
    return jnp.matmul(a, b, precision=p)


def _rmsnorm(x, scale):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + EPS)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x):
    """Rotate each (even, odd) pair of the head dim by position · freq."""
    s, hd = x.shape[1], x.shape[-1]
    freq = 1.0 / THETA ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    c, n = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(
        jnp.float32)
    out = jnp.stack([x1 * c - x2 * n, x2 * c + x1 * n], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def forward(base, lora, x, config: dict):
    """Logits of a batch of feature rows, in the adapters' dtype."""
    z, dt = _dims(config), jax.tree.leaves(lora)[0].dtype
    w = jax.tree.map(lambda a: a.astype(dt), base)
    blk = w["segments"][0][0]
    for path in TARGETS:
        group, name = path.split("/")
        ab = lora["lora"][PREFIX + path]
        blk[group][name] = blk[group][name] + jnp.einsum(
            "lir,lro->lio", ab["lora_a"], ab["lora_b"])
    p = jax.nn.sigmoid(x.astype(jnp.float32))
    tok = jnp.clip(jnp.floor(p * z["v"]), 0, z["v"] - 1).astype(jnp.int32)
    table = w["embed"]["table"]
    h = table[tok]
    b, s, hh, hd = h.shape[0], z["s"], z["h"], z["hd"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(z["l"]):
        L = jax.tree.map(lambda a: a[i], blk)
        a = _rmsnorm(h, L["norm1"]["scale"])
        q = _rope(_mm(a, L["mixer"]["wq"]).reshape(b, s, hh, hd))
        k = _rope(_mm(a, L["mixer"]["wk"]).reshape(b, s, hh, hd))
        v = _mm(a, L["mixer"]["wv"]).reshape(b, s, hh, hd)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=lax.Precision.HIGHEST) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        att = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(dt)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v,
                       precision=lax.Precision.HIGHEST)
        h = h + _mm(o.reshape(b, s, hh * hd), L["mixer"]["wo"])
        a = _rmsnorm(h, L["norm2"]["scale"])
        g = _mm(a, L["ffn"]["w_gate"])
        h = h + _mm(jax.nn.silu(g) * _mm(a, L["ffn"]["w_up"]),
                    L["ffn"]["w_down"])
    h = _rmsnorm(h, w["final_norm"]["scale"])
    return _mm(h[:, -1], table.T)[:, :z["c"]]


def loss(params, xb, yb, config: dict):
    logits = forward(base_params(config), params, xb, config)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=-1))


# ---- FLOPs -----------------------------------------------------------------


def forward_flops(config: dict) -> int:
    """FLOPs of one example's forward pass: the projections, the MLP, both
    attention products over the whole (unmasked) square, and the head."""
    z = _dims(config)
    hh = z["h"] * z["hd"]
    per_layer = z["s"] * (4 * z["d"] * hh + 3 * z["d"] * z["f"]
                          + 2 * z["s"] * hh)
    return 2 * (z["l"] * per_layer + z["d"] * z["v"])


def train_flops(config: dict) -> int:
    return 3 * forward_flops(config)


def shrink(config: dict) -> dict:
    """Already a CPU size."""
    return copy.deepcopy(config)
