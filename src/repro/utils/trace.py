"""The names the program's tracing uses, and the two ways it marks them.

Device scopes: :func:`scope` wraps one layer of the traced federated round
in ``jax.named_scope("fed.<layer>")``. A scope only sets the ``op_name``
metadata of the operations traced inside it: it adds no call boundary to
the jaxpr and leaves the compiled arithmetic as it was. The name survives
compilation and transforms (``vmap(fed.local_sgd)/while/body/…``,
``transpose(jvp(fed.local_sgd))``), so a profiler trace attributes each
device operation to its layer by a substring match on its op_name; a
``while`` and the operations of its body both carry the scope.

Host spans: :func:`span` is a ``jax.profiler.TraceAnnotation`` named
``fed.<name>``, which lands on the host plane of a profiler trace, on the
device operations' clock. With no profiler running it costs about a
microsecond.

The model's own layers have scopes too, nested inside ``fed.local_sgd``
and ``fed.eval``: ``fed.mla`` (latent attention, and through the names
the backward pass inherits, its gradient), ``fed.moe`` (router, sort,
grouped matmuls, combine, shared experts), ``fed.moe_gmm`` (the grouped
matmuls alone, inside ``fed.moe``) and ``fed.lm_head`` (the token-chunked
head with its loss or its hits).

Counters: ``Session.counters``, keyed by the names below, worked out on
the host from the round count and the ledger the round carry accumulates;
reading one waits for the device, so nothing reads them per dispatch. The
model's layers have none: a dropless expert layer's work is fixed by the
schedule (tokens × experts per token a step), so a counter worked out
from it would repeat the host's own count.
"""
from __future__ import annotations

import jax

PREFIX = "fed."

# ---- device scopes: the layers of one round, and the evaluation --------
LOCAL_SGD = "local_sgd"        # round keys, broadcast, the trainers' K steps
ESTIMATE = "estimate"          # stale delta, Strategy.estimate, train/estimate select
AGGREGATE = "aggregate"        # uplink channel, agg mask, aggregate/merge, new params
HISTORY = "history"            # update_history, update_extra_history
POLICY = "policy"              # budget ctx, policy.decide, device advance, ledger
EVAL = "eval"                  # the evaluation's jitted apply
# ---- device scopes of the model's own layers ----------------------------
MLA = "mla"                    # multi-head latent attention
MOE = "moe"                    # router, sort, grouped matmuls, combine, shared
MOE_GMM = "moe_gmm"            # the expert grouped matmuls alone
LM_HEAD = "lm_head"            # token-chunked head: the loss or the hits
SCOPES = (LOCAL_SGD, ESTIMATE, AGGREGATE, HISTORY, POLICY, EVAL, MLA, MOE,
          MOE_GMM, LM_HEAD)

# ---- host spans ---------------------------------------------------------
RUN = "run"                    # Session.run
DISPATCH = "dispatch"          # one span-runner or round-fn call
EVAL_BATCH = "eval_batch"      # one evaluation batch, its host sync included
CALLBACKS = "callbacks"        # one firing of a callback hook
SPANS = (RUN, DISPATCH, EVAL, EVAL_BATCH, CALLBACKS)   # EVAL is both

# ---- host counters (keys of Session.counters) ---------------------------
#: client-rounds the executor ran through local SGD, whether or not the
#: result was kept: the trained client-rounds in the flat executors,
#: rounds × N in the hierarchical and async executors
LOCAL_SGD_CLIENT_ROUNDS = "local_sgd_client_rounds"
COUNTERS = (LOCAL_SGD_CLIENT_ROUNDS,)


def scope(layer: str):
    """``jax.named_scope`` for one layer of the traced round or model."""
    if layer not in SCOPES:
        raise ValueError(f"unknown scope {layer!r}; known: {SCOPES}")
    return jax.named_scope(PREFIX + layer)


def span(name: str):
    """A host span in the profiler's trace."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    return jax.profiler.TraceAnnotation(PREFIX + name)
