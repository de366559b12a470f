"""The plain CC-FedAvg reference at a small width of the ``resnet18gn``
family, against the same rounds computed by hand for two clients, and the
family's weights and loss against the program's model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.cells import family, resolve
from bench.reference import resnet18gn
from bench.reference.round import run_rounds

W, HW, C, K, B, LR = 8, 8, 10, 2, 4, 0.05
FAMILY = family(resolve("silo8.cc_power"))
CONFIG = {"model": {"family": "resnet18gn", "arch": "resnet18", "width": W,
                    "groups": 8, "image_size": HW, "channels": 3,
                    "n_classes": C}}


def loss(p, xb, yb):
    return FAMILY.loss(p, xb, yb, CONFIG)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(11)
    kp, kx, ky = jax.random.split(key, 3)
    params = FAMILY.init_params(CONFIG, kp)
    x = jax.random.normal(kx, (2, 12, HW, HW, 3))
    y = jax.random.randint(ky, (2, 12), 0, C)
    sizes = jnp.array([12, 9], jnp.int32)
    return params, x, y, sizes


def _sgd_by_hand(params, key, cx, cy, size):
    """K plain SGD steps, written out step by step."""
    p = params
    for _ in range(K):
        key, sk = jax.random.split(key)
        idx = jax.random.randint(sk, (B,), 0, 2 ** 30) % size
        logits = None

        def loss(q):
            logits = resnet18gn.forward(q, cx[idx])
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(lp[jnp.arange(B), cy[idx]])

        g = jax.grad(loss)(p)
        p = jax.tree.map(lambda a, b: a - LR * b, p, g)
        del logits
    return p


def _sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def _max_diff(a, b):
    return max(float(jnp.max(jnp.abs(u - v)))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_two_rounds_two_clients_by_hand(setup):
    params, x, y, sizes = setup
    key = jax.random.PRNGKey(5)
    sel = np.ones((2, 2), bool)
    train = np.array([[True, True], [True, False]])
    got, hist, trained = run_rounds(params, key, x, y, sizes, sel, train,
                                    loss=loss, local_steps=K, batch_size=B,
                                    lr=LR)
    # round 0: both clients train from the global model
    k0 = jax.random.split(key, 3)
    d0 = [_sub(_sgd_by_hand(params, k0[1 + i], x[i], y[i], sizes[i]),
               params) for i in range(2)]
    p1 = jax.tree.map(lambda p, a, b: p + (a + b) / 2, params, *d0)
    # round 1: client 0 trains, client 1 replays its round-0 update
    k1 = jax.random.split(k0[0], 3)
    d1 = _sub(_sgd_by_hand(p1, k1[1], x[0], y[0], sizes[0]), p1)
    p2 = jax.tree.map(lambda p, a, b: p + (a + b) / 2, p1, d1, d0[1])
    assert _max_diff(got, p2) < 1e-5
    assert _max_diff(hist[0], d1) < 1e-5
    assert _max_diff(hist[1], d0[1]) < 1e-6
    assert list(trained) == [2, 1]


def test_int8_history_by_hand(setup):
    params, x, y, sizes = setup
    key = jax.random.PRNGKey(2)
    sel = np.ones((2, 2), bool)
    train = np.array([[True, True], [False, True]])
    _, hist32, _ = run_rounds(params, key, x, y, sizes, sel[:1], train[:1],
                              loss=loss, local_steps=K, batch_size=B, lr=LR)
    got, hist8, _ = run_rounds(params, key, x, y, sizes, sel, train,
                               loss=loss, local_steps=K, batch_size=B, lr=LR,
                               history="int8")
    # client 0's row after round 0: one scale over all of its leaves
    d = jax.tree.leaves(hist32[0])
    scale = np.float32(max(float(jnp.max(jnp.abs(l))) for l in d)) / 127
    hand = [np.clip(np.round(np.asarray(l) * (np.float32(1) / scale)),
                    -127, 127) * scale for l in d]
    for h, l in zip(hand, jax.tree.leaves(hist8[0])):
        np.testing.assert_allclose(np.asarray(l), h, rtol=0, atol=1e-7)
        assert len(np.unique(np.round(np.asarray(l) / scale))) <= 255


def test_bfloat16_runs_in_bfloat16(setup):
    params, x, y, sizes = setup
    got, _, _ = run_rounds(params, jax.random.PRNGKey(0), x, y, sizes,
                           np.ones((1, 2), bool), np.ones((1, 2), bool),
                           loss=loss, local_steps=K, batch_size=B, lr=LR,
                           dtype="bfloat16")
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(got))


def test_forward_matches_the_program_model(setup):
    params, x, y, _ = setup
    model = FAMILY.build_model(CONFIG)
    assert (jax.tree.structure(model.init(jax.random.PRNGKey(0)))
            == jax.tree.structure(params))
    ref = resnet18gn.forward(params, x[0])
    np.testing.assert_allclose(model.apply(params, x[0]), ref, rtol=1e-4,
                               atol=1e-4)
    logp = jax.nn.log_softmax(model.apply(params, x[0]))
    np.testing.assert_allclose(
        -jnp.mean(logp[jnp.arange(12), y[0]]), loss(params, x[0], y[0]),
        rtol=1e-4, atol=1e-5)
