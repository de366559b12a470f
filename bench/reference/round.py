"""Plain CC-FedAvg rounds (arXiv:2212.13679, Alg. 1, Strategy 3).

One client at a time, no vmap, no kernels, no session. Each round:

1. the round key splits into the next round key and one key per client;
2. every selected client that trains runs K steps of minibatch SGD from
   the global model; each step splits its key, draws ``B`` indices as
   ``randint(0, 2**30) % size`` and takes one gradient step of rate
   ``lr`` on the model family's plain loss (Eq. 2;
   ``bench/families/<family>.py``);
3. a selected client that does not train replays its stored update
   Δ_{t−1}^i (Strategy 3; zero until it has trained once);
4. the global model moves by the plain mean of the selected clients'
   updates (Eq. 3);
5. a client that trained stores its new update x_K^i − x_t.

With ``history="int8"`` the stored update is kept as the deployment keeps
it: one int8 row per client over the flattened model (leaves in
``jax.tree`` order) with one scale, scale = max(max|Δ|, 1e−12)/127 and
q = clip(round(Δ/scale), ±127); replay reads q·scale. The round's own
aggregate uses the unrounded update of a client that trained.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


QMAX = 127.0


def client_keys(key, n: int):
    ks = jax.random.split(key, n + 1)
    return ks[0], ks[1:]


@functools.lru_cache(maxsize=8)
def local_sgd_fn(loss, k_steps: int, batch: int, lr: float,
                 dtype_name: str):
    """A jitted K-step SGD of one client on ``loss(params, xb, yb)``,
    computed in ``dtype_name``."""
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def local_sgd(params, key, cx, cy, size):
        p = jax.tree.map(lambda a: a.astype(dtype), params)
        for _ in range(k_steps):
            key, sk = jax.random.split(key)
            idx = jax.random.randint(sk, (batch,), 0, 2 ** 30) % size
            g = jax.grad(loss)(p, cx[idx].astype(dtype), cy[idx])
            p = jax.tree.map(lambda a, b: a - jnp.asarray(lr, dtype) * b,
                             p, g)
        return p

    return local_sgd


def _quantize(delta):
    """The int8 row of one client's update, returned dequantized."""
    leaves, treedef = jax.tree.flatten(delta)
    mx = max(float(jnp.max(jnp.abs(l.astype(jnp.float32)))) for l in leaves)
    scale = np.float32(max(mx, 1e-12)) / np.float32(QMAX)
    inv = np.float32(1.0) / scale
    deq = [jnp.clip(jnp.round(l.astype(jnp.float32) * inv), -QMAX, QMAX)
           * scale for l in leaves]
    return jax.tree.unflatten(treedef, deq)


def run_rounds(params, key, x, y, sizes, selection, training, *, loss,
               local_steps: int, batch_size: int, lr: float,
               history: str = "f32", dtype: str = "float32",
               on_round=None):
    """Run ``len(selection)`` rounds from ``params``, each client's local
    SGD on ``loss(params, xb, yb)``.

    Returns ``(params, deltas, trained)``: the global model, each client's
    stored update as a tree (dequantized where the history is int8; zeros
    for a client that never trained), and each client's count of rounds
    trained. ``dtype="bfloat16"`` computes everything in bfloat16.
    ``on_round(t, params, deltas, trained)``, where given, sees the same
    after each round, ``t`` rounds in."""
    sgd = local_sgd_fn(loss, local_steps, batch_size, float(lr), dtype)
    dt = jnp.dtype(dtype)
    params = jax.tree.map(lambda a: jnp.asarray(a, dt), params)
    n = len(sizes)
    zeros = jax.tree.map(jnp.zeros_like, params)
    hist = [zeros] * n
    trained = np.zeros(n, np.int64)
    selection = np.asarray(selection, bool)
    training = np.asarray(training, bool)
    for t in range(selection.shape[0]):
        key, ck = client_keys(key, n)
        new_hist = list(hist)
        ups = []
        for i in range(n):
            if not selection[t, i]:
                continue
            if training[t, i]:
                local = sgd(params, ck[i], x[i], y[i], sizes[i])
                d = jax.tree.map(jnp.subtract, local, params)
                new_hist[i] = _quantize(d) if history == "int8" else d
                trained[i] += 1
            else:
                d = hist[i]
            ups.append(d)
        if ups:
            total = functools.reduce(
                lambda a, b: jax.tree.map(jnp.add, a, b), ups)
            params = jax.tree.map(
                lambda p, s: p + s / jnp.asarray(len(ups), dt), params, total)
        hist = new_hist
        if on_round is not None:
            on_round(t + 1, params, hist, trained)
    return params, hist, trained
