"""A model's frozen base is an argument of every jitted program of a
session, never a constant folded into it, and never part of the trained
state: the lowered round and evaluation programs of a LoRA model stay the
same size when its base grows twentyfold and more."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.session import Session
from repro.core.evaluation import jitted_apply
from repro.core.rounds import FedConfig
from repro.core.schedules import make_plan
from repro.data.federated import FederatedData
from repro.models.decoder import model_init
from repro.models.lora import lora_classifier
from repro.models.simple import make_classifier
from repro.models.zoo import make_lm, zoo_arch_config

N, M, S = 4, 8, 8


def _lm(vocab: int):
    cfg = zoo_arch_config("moe", width=2, n_layers=2, vocab=vocab)
    base = model_init(jax.random.PRNGKey(0), cfg)
    return make_lm(cfg, base, rank=2), vocab


def _mlp_lora(hidden: int):
    base = make_classifier("mlp", input_shape=(S,), n_classes=4,
                           width=hidden // 4)
    return lora_classifier(base, jax.random.PRNGKey(0), 2), 4


def _session(model, n_labels):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    if model.hits is not None:          # sequences as labels, topics as x
        x = jnp.zeros((N, M), jnp.int32)
        y = jax.random.randint(k1, (N, M, S + 1), 0, n_labels)
    else:
        x = jax.random.normal(k1, (N, M, S))
        y = jax.random.randint(k2, (N, M), 0, n_labels)
    data = FederatedData(x, y, jnp.full((N,), M, jnp.int32), n_labels)
    fed = FedConfig(strategy="cc", local_steps=1, batch_size=2, lr=0.1)
    plan = make_plan("adhoc", np.ones(N), 4, seed=0)
    return Session(model, data, fed, plan, x_test=x[0], y_test=y[0],
                   eval_every=2)


def _program_bytes(sess) -> tuple[int, int]:
    """Lowered text of the session's span program and evaluation."""
    run = sess._get_span_runner()
    span = run.func.lower(sess.state, sess._sel[:1], sess.k_active,
                          **run.keywords).as_text()
    m = sess.model
    base = () if m.base is None else (m.base,)
    if m.hits is not None:
        ev = jitted_apply(m.hits).lower(sess.state["params"], sess.x_test,
                                       sess.y_test, *base)
    else:
        ev = jitted_apply(m.apply).lower(sess.state["params"], sess.x_test,
                                         *base)
    return len(span), len(ev.as_text())


@pytest.mark.parametrize("build,small,large", [
    (_lm, 64, 32000),            # vocabulary: embedding and head
    (_mlp_lora, 8, 800),         # hidden width of the wrapped MLP
])
def test_programs_do_not_grow_with_the_base(build, small, large):
    sizes = []
    for arg in (small, large):
        model, n_labels = build(arg)
        sess = _session(model, n_labels)
        base_bytes = sum(l.nbytes for l in jax.tree.leaves(model.base))
        assert set(sess.state["params"]) == {"lora"}
        sizes.append((base_bytes, _program_bytes(sess)))
    (b_small, p_small), (b_large, p_large) = sizes
    assert b_large > 20 * b_small
    for a, b in zip(p_small, p_large):
        # only the shapes' digits in the text change: a base folded in
        # as a constant would add at least its own bytes
        assert abs(a - b) < max(0.02 * a, 1000), (p_small, p_large)
        assert b < b_large


def test_a_session_runs_and_evaluates_a_token_model():
    model, vocab = _lm(64)
    sess = _session(model, vocab)
    sess.run(n_rounds=2)
    acc = sess.metrics.last("test_acc")
    assert 0.0 <= acc <= 1.0
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(sess.state["params"]))
