"""The comparison that decides ``correct``.

What is compared is what the timed path produced in its first rounds:
the state that the window's own span program leaves after the cell's
first ``rounds`` rounds (``bench/limits/<cell>.json``), at the timed
sizes, which ``Session.run`` then hands on to the measured window. The plain
reference (``bench/reference``, on the loss of the cell's model family)
follows the same rounds from the same weights, data, keys and schedule,
in float32 at ``"highest"`` precision.
Three numbers come out, each held to the cell's limit
(``bench/limits/<cell>.json``):

* ``param_change_gap``: by the worst leaf of the model, the gap between
  the norm of the program's change of the global model over the span and
  the reference's, over the reference's norm of that leaf or of the
  median leaf, whichever is larger. Leaves whose change in the reference
  is under a thousandth of the median leaf's are left out (rounding alone
  moves them).
* ``history_gap``: the same, by the worst (client, leaf), for each
  client's stored update Δ^i after the span (dequantized where the
  history is int8).
* ``train_count_gap``: the largest difference, over clients, between the
  rounds the program's ledger says a client trained and the rounds the
  schedule says it trains. It is exact: limit 0.

The lower-precision control (:func:`control_outputs`) puts the reference
in the program's place, computed in bfloat16; under the cell's limits it
has to come out not correct (``bench/calibrate.py`` reads it on the chip,
``bench/tests/test_faults.py`` at a small size).
"""
from __future__ import annotations

import functools
import json

import jax
import numpy as np

NUMBERS = ("param_change_gap", "history_gap", "train_count_gap")
#: a leaf whose reference change is under this share of the median
#: leaf's is left out of a gap
NEGLIGIBLE = 1e-3


def leaves64(tree) -> list[np.ndarray]:
    return [np.asarray(l, np.float64) for l in jax.tree.leaves(tree)]


def history_rows(deltas, params_like) -> list[list[np.ndarray]]:
    """Each client's stored update as a list of leaves (jax.tree order).

    ``deltas`` is a client-stacked tree like the params, or the int8
    layout ``{"payload": (N, P_pad) int8, "scales": (N,)}`` whose rows are
    the model's leaves flattened in ``jax.tree`` order and zero-padded."""
    shapes = [np.shape(l) for l in jax.tree.leaves(params_like)]
    if isinstance(deltas, dict) and set(deltas) == {"payload", "scales"}:
        rows = (np.asarray(deltas["payload"], np.float64)
                * np.asarray(deltas["scales"], np.float64)[:, None])
        out = []
        for row in rows:
            leaves, off = [], 0
            for s in shapes:
                size = int(np.prod(s))
                leaves.append(row[off:off + size].reshape(s))
                off += size
            out.append(leaves)
        return out
    stacked = leaves64(deltas)
    return [[l[i] for l in stacked] for i in range(stacked[0].shape[0])]


def worst_norm_gap(ours: list[np.ndarray], ref: list[np.ndarray]) -> float:
    """max over leaves of | |ours| − |ref| | / max(|ref|, median |ref|)."""
    a = np.array([np.linalg.norm(x) for x in ours])
    r = np.array([np.linalg.norm(x) for x in ref])
    med = float(np.median(r))
    if med == 0.0:
        return 0.0 if not a.any() else float("inf")
    keep = r >= NEGLIGIBLE * med
    return float(np.max(np.abs(a[keep] - r[keep])
                        / np.maximum(r[keep], med)))


def numbers(params0, got: dict, ref: dict) -> dict:
    """The three compared numbers. ``got`` and ``ref`` each hold
    ``params`` (global model after the span), ``history`` (per client,
    a list of leaves) and ``trained`` (rounds trained per client)."""
    p0 = leaves64(params0)
    change = [g - x for g, x in zip(leaves64(got["params"]), p0)]
    ref_change = [r - x for r, x in zip(leaves64(ref["params"]), p0)]
    hist = max(worst_norm_gap(g, r)
               for g, r in zip(got["history"], ref["history"]))
    return {
        "param_change_gap": worst_norm_gap(change, ref_change),
        "history_gap": hist,
        "train_count_gap": float(np.max(np.abs(
            np.asarray(got["trained"], np.int64)
            - np.asarray(ref["trained"], np.int64)))),
    }


@functools.lru_cache(maxsize=8)
def _loss(family, config_json: str):
    """``family.loss`` at one configuration, as ``loss(params, xb, yb)``;
    the same function for the same configuration, so that the
    reference's compiled local SGD is reused."""
    config = json.loads(config_json)

    def loss(params, xb, yb):
        return family.loss(params, xb, yb, config)

    return loss


def reference_snapshots(cell, inputs, rounds, dtype: str = "float32"
                        ) -> dict:
    """The plain reference on ``inputs``, its outputs after each count of
    rounds in ``rounds``, keyed by that count."""
    from bench.cells import family
    from bench.reference.round import run_rounds
    cfg = cell.config
    tr, ex = cfg["training"], cfg["execution"]
    want, out = set(rounds), {}

    def keep(t, params, hist, trained):
        if t in want:
            out[t] = {"params": jax.device_get(params),
                      "history": [leaves64(h) for h in hist],
                      "trained": trained.copy()}

    last = max(want)
    run_rounds(inputs.params, inputs.key, inputs.x, inputs.y, inputs.sizes,
               inputs.selection[:last], inputs.training[:last],
               local_steps=tr["local_steps"], batch_size=tr["batch_size"],
               lr=tr["lr"],
               loss=_loss(family(cell), json.dumps(cfg, sort_keys=True)),
               history="int8" if ex["compress"] == "int8" else "f32",
               dtype=dtype, on_round=keep)
    return out


def reference_outputs(cell, inputs, dtype: str = "float32") -> dict:
    """The plain reference over the rounds the cell's check compares."""
    rounds = int(cell.limits["rounds"])
    return reference_snapshots(cell, inputs, [rounds], dtype)[rounds]


def control_outputs(cell, inputs) -> dict:
    """The control: the reference computed in bfloat16, in the shape of
    what the program hands the check."""
    return reference_outputs(cell, inputs, dtype="bfloat16")


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per number, its value beside its limit."""
    out = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(np.isfinite(values[k]) and values[k] <= limits[k]
             for k in NUMBERS)
    return ok, out
