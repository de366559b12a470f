"""Test-set evaluation shared by the engine shim and the Session API.

The old ``engine.evaluate`` wrapped ``model.apply`` in ``jax.jit`` on every
call, so every evaluation re-traced the model. The jitted apply is now
cached per model apply-function, so a run with hundreds of eval points
traces once per (model, batch-shape). The apply runs in the ``fed.eval``
device scope, and each batch, its host sync included, is a
``fed.eval_batch`` host span (:mod:`repro.utils.trace`).

Accuracy is averaged over targets: an example has one (a class label) or
many (a sequence's next tokens). A model with its own ``hits`` counts them
inside one jitted program per batch, chunking its logits as it needs; the
others' top-1 hits come from their logits. A model's frozen ``base`` is an
argument of the jitted program, like the params.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.simple import Classifier, frozen
from repro.utils.trace import EVAL, EVAL_BATCH, scope, span

#: jitted apply per model.apply function (identity-keyed; bounded so a
#: sweep building many models cannot grow it without limit)
_APPLY_CACHE: dict[Callable, Callable] = {}
_APPLY_CACHE_MAX = 64


def jitted_apply(apply_fn: Callable) -> Callable:
    """``apply_fn`` (a model's ``apply``, or its ``hits``) jitted in the
    ``fed.eval`` scope, cached by the function."""
    fn = _APPLY_CACHE.get(apply_fn)
    if fn is None:
        if len(_APPLY_CACHE) >= _APPLY_CACHE_MAX:
            _APPLY_CACHE.clear()

        @functools.wraps(apply_fn)
        def scoped(*args):
            with scope(EVAL):
                return apply_fn(*args)
        fn = _APPLY_CACHE[apply_fn] = jax.jit(scoped)
    return fn


def evaluate(model: Classifier, params, x_test, y_test,
             batch: int = 512) -> float:
    """Top-1 accuracy over the test set's targets, batched."""
    n = x_test.shape[0]
    correct = targets = 0
    base = frozen(model)
    run = jitted_apply(model.apply if model.hits is None else model.hits)
    for i in range(0, n, batch):
        xb, yb = x_test[i: i + batch], y_test[i: i + batch]
        with span(EVAL_BATCH):
            if model.hits is not None:
                right, count = run(params, xb, yb, *base)
                correct += int(right)
                targets += int(count)
            else:
                logits = run(params, xb, *base)
                correct += int(jnp.sum(jnp.argmax(logits, -1) == yb))
                targets += yb.size
    return correct / targets
