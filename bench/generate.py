"""The benchmark's one generator: inputs, weights and budget schedules.

Everything a run feeds the program is made here from the cell's
configuration, its traffic mix and ``--seed``, and nothing is taken from
the program: the same seed gives the same inputs, and the reference is
handed exactly what the program is handed.

* Data: CIFAR-shaped images made on the device in one jitted call. Each
  class has a smooth template (a 4×4 random pattern, upsampled) and an
  image is its class's template plus Gaussian noise. Client ``i`` of
  ``N`` holds ``n_local`` images: a ``gamma`` share with labels uniform
  over all classes and the rest from its own contiguous block of
  ``n_classes / N`` classes (the paper's γ-heterogeneity, §VI-A). Every
  client holds the same number of images whatever the seed.
* Weights: the plain reference's initialisation, on the device in one
  jitted call.
* Budget schedule: a (rounds × clients) training table.
  ``"round_robin"`` is the paper's round-robin schedule (arXiv:2212.13679,
  Fig. 1a): client ``i`` trains in round ``t`` when ``t mod W_i`` equals
  its offset, ``W_i = round(1 / p_i)``, with the offsets the mix lists;
  ``"full"`` trains every client every round. The table does not depend
  on ``--seed``: every run of a cell does the same work, and the program
  compiled for it is the same.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import resnet18gn


def seed_words(seed: int, n: int = 4) -> list[int]:
    """``n`` 31-bit words from any whole-number seed, however large."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    ss = np.random.SeedSequence(seed)
    return [int(w) & 0x7FFFFFFF for w in ss.generate_state(n)]


def budgets(traffic: dict, n_clients: int) -> np.ndarray:
    """Per-client budgets p_i: ``"power"`` is the paper's
    (1/2)^⌊β·i/N⌋; ``"uniform"`` is 1 for everyone."""
    kind = traffic["budget"]
    if kind == "power":
        i = np.arange(n_clients)
        return 0.5 ** np.floor(traffic["beta"] * i / n_clients)
    if kind == "uniform":
        return np.ones(n_clients)
    raise ValueError(f"unknown budget {kind!r}")


def training_table(traffic: dict, n_clients: int) -> np.ndarray:
    """The (plan_rounds, N) bool table of who trains in which round."""
    rounds = int(traffic["plan_rounds"])
    p = budgets(traffic, n_clients)
    kind = traffic["schedule"]
    if kind == "full":
        if not (p == 1).all():
            raise ValueError("schedule 'full' needs budgets of 1")
        return np.ones((rounds, n_clients), bool)
    if kind != "round_robin":
        raise ValueError(f"unknown schedule {kind!r}")
    period = np.round(1.0 / p).astype(int)
    offsets = np.asarray(traffic["offsets"], int)
    if offsets.shape != (n_clients,) or not (
            (0 <= offsets) & (offsets < period)).all():
        raise ValueError(f"offsets {offsets.tolist()} must give each of "
                         f"{n_clients} clients one in [0, W_i), W = "
                         f"{period.tolist()}")
    t = np.arange(rounds)[:, None]
    return (t % period[None, :]) == offsets[None, :]


@functools.partial(jax.jit, static_argnames=(
    "n_clients", "n_local", "n_test", "hw", "channels", "n_classes",
    "gamma", "noise"))
def make_data(key, *, n_clients, n_local, n_test, hw, channels, n_classes,
              gamma, noise):
    """Client images and labels, and a test split, all on the device."""
    k_t, k_iid, k_sh, k_x, k_ty, k_tx = jax.random.split(key, 6)
    tmpl = jax.random.normal(k_t, (n_classes, 4, 4, channels))
    tmpl = jnp.repeat(jnp.repeat(tmpl, hw // 4, axis=1), hw // 4, axis=2)
    n_iid = int(round(gamma * n_local))
    y_iid = jax.random.randint(k_iid, (n_clients, n_iid), 0, n_classes)
    lo = (jnp.arange(n_clients) * n_classes) // n_clients
    hi = ((jnp.arange(n_clients) + 1) * n_classes) // n_clients
    u = jax.random.uniform(k_sh, (n_clients, n_local - n_iid))
    y_sh = lo[:, None] + jnp.floor(u * (hi - lo)[:, None]).astype(jnp.int32)
    y = jnp.concatenate([y_iid, y_sh], axis=1).astype(jnp.int32)
    x = tmpl[y] + noise * jax.random.normal(
        k_x, (n_clients, n_local, hw, hw, channels))
    y_test = jax.random.randint(k_ty, (n_test,), 0, n_classes
                                ).astype(jnp.int32)
    x_test = tmpl[y_test] + noise * jax.random.normal(
        k_tx, (n_test, hw, hw, channels))
    sizes = jnp.full((n_clients,), n_local, jnp.int32)
    return x, y, sizes, x_test, y_test


@functools.partial(jax.jit, static_argnames=("channels", "n_classes",
                                             "width"))
def make_weights(key, *, channels, n_classes, width):
    return resnet18gn.init(key, channels, n_classes, width)


@dataclass
class Inputs:
    """What one run hands the program, and the reference after it."""
    x: jax.Array            # (N, n_local, hw, hw, C) f32
    y: jax.Array            # (N, n_local) int32
    sizes: jax.Array        # (N,) int32
    x_test: jax.Array
    y_test: jax.Array
    params: dict            # initial global model
    key: jax.Array          # the round-key chain's first key
    selection: np.ndarray   # (plan_rounds, N) bool
    training: np.ndarray    # (plan_rounds, N) bool
    budgets: np.ndarray     # (N,)


def make_inputs(config: dict, traffic: dict, seed: int) -> Inputs:
    fed, model = config["federation"], config["model"]
    w_data, w_weights, w_key, _ = seed_words(seed)
    x, y, sizes, x_test, y_test = make_data(
        jax.random.PRNGKey(w_data), n_clients=fed["n_clients"],
        n_local=fed["samples_per_client"], n_test=fed["test_samples"],
        hw=model["image_size"], channels=model["channels"],
        n_classes=model["n_classes"], gamma=fed["gamma"],
        noise=fed["noise"])
    params = make_weights(jax.random.PRNGKey(w_weights),
                          channels=model["channels"],
                          n_classes=model["n_classes"],
                          width=model["width"])
    n = fed["n_clients"]
    training = training_table(traffic, n)
    selection = np.ones_like(training)
    return Inputs(x=x, y=y, sizes=sizes, x_test=x_test, y_test=y_test,
                  params=params, key=jax.random.PRNGKey(w_key),
                  selection=selection, training=training,
                  budgets=budgets(traffic, n))
