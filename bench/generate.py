"""The benchmark's one generator: inputs, weights and budget schedules.

Everything a run feeds the program is made here from the cell's
configuration, its traffic mix and ``--seed``, and nothing is taken from
the program: the same seed gives the same inputs, and the reference is
handed exactly what the program is handed.

* Seed words: ``--seed`` gives four 31-bit words: the data's, the
  weights', the round key's, and one spare.
* Data and weights: the cell's model family makes them on the device
  (``bench/families/<family>.py``: ``make_data`` from the data word,
  ``init_params`` from a key of the weights' word).
* Budget schedule: a (rounds × clients) training table.
  ``"round_robin"`` is the paper's round-robin schedule (arXiv:2212.13679,
  Fig. 1a): client ``i`` trains in round ``t`` when ``t mod W_i`` equals
  its offset, ``W_i = round(1 / p_i)``, with the offsets the mix lists;
  ``"full"`` trains every client every round. The table does not depend
  on ``--seed``: every run of a cell does the same work, and the program
  compiled for it is the same.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np


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


@dataclass
class Inputs:
    """What one run hands the program, and the reference after it."""
    x: jax.Array            # (N, n_local, ...) client examples
    y: jax.Array            # (N, n_local) int32 labels
    sizes: jax.Array        # (N,) int32
    x_test: jax.Array
    y_test: jax.Array
    params: dict            # initial global model
    key: jax.Array          # the round-key chain's first key
    selection: np.ndarray   # (plan_rounds, N) bool
    training: np.ndarray    # (plan_rounds, N) bool
    budgets: np.ndarray     # (N,)


def make_inputs(family, config: dict, traffic: dict, seed: int) -> Inputs:
    """The inputs of one run: ``family`` is the cell's model family
    module (``bench.cells.family``)."""
    w_data, w_weights, w_key, _ = seed_words(seed)
    x, y, sizes, x_test, y_test = family.make_data(config, (w_data,))
    params = family.init_params(config, jax.random.PRNGKey(w_weights))
    n = config["federation"]["n_clients"]
    training = training_table(traffic, n)
    selection = np.ones_like(training)
    return Inputs(x=x, y=y, sizes=sizes, x_test=x_test, y_test=y_test,
                  params=params, key=jax.random.PRNGKey(w_key),
                  selection=selection, training=training,
                  budgets=budgets(traffic, n))
