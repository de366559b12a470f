"""Test-set evaluation shared by the engine shim and the Session API.

The old ``engine.evaluate`` wrapped ``model.apply`` in ``jax.jit`` on every
call, so every evaluation re-traced the model. The jitted apply is now
cached per model apply-function, so a run with hundreds of eval points
traces once per (model, batch-shape). The apply runs in the ``fed.eval``
device scope, and each batch, its host sync included, is a
``fed.eval_batch`` host span (:mod:`repro.utils.trace`).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.simple import Classifier
from repro.utils.trace import EVAL, EVAL_BATCH, scope, span

#: jitted apply per model.apply function (identity-keyed; bounded so a
#: sweep building many models cannot grow it without limit)
_APPLY_CACHE: dict[Callable, Callable] = {}
_APPLY_CACHE_MAX = 64


def jitted_apply(apply_fn: Callable) -> Callable:
    fn = _APPLY_CACHE.get(apply_fn)
    if fn is None:
        if len(_APPLY_CACHE) >= _APPLY_CACHE_MAX:
            _APPLY_CACHE.clear()

        @functools.wraps(apply_fn)
        def scoped(params, x):
            with scope(EVAL):
                return apply_fn(params, x)
        fn = _APPLY_CACHE[apply_fn] = jax.jit(scoped)
    return fn


def evaluate(model: Classifier, params, x_test, y_test,
             batch: int = 512) -> float:
    """Top-1 accuracy over the test set, batched."""
    n = x_test.shape[0]
    correct = 0
    apply = jitted_apply(model.apply)
    for i in range(0, n, batch):
        with span(EVAL_BATCH):
            logits = apply(params, x_test[i: i + batch])
            correct += int(jnp.sum(jnp.argmax(logits, -1)
                                   == y_test[i: i + batch]))
    return correct / n
