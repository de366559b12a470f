"""Faults planted under the timed path, to show that ``correct`` catches them.

Each fault patches the program while a run builds, compiles and drives
its session; the comparison must then come out not correct. They serve
the fault tests (``bench/tests/test_faults.py``) and the readings that
bound each limit from above (``bench/calibrate.py``). The benchmark's
own runs never plant one.

* ``unchanged``: every span returns the state it was given.
* ``half_batch``: local SGD takes its loss over half of each minibatch.
* ``altered``: the global model a span returns is altered where it is
  produced: its update over the span is scaled by 1.5.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "altered")
RUNNER_FACTORIES = ("make_policy_span_runner",)


def _wrap_runners(session_mod, wrap):
    saved = {}
    for name in RUNNER_FACTORIES:
        orig = getattr(session_mod, name)
        saved[name] = orig

        def factory(*a, _orig=orig, **k):
            return wrap(_orig(*a, **k))

        setattr(session_mod, name, factory)
    return saved


@contextlib.contextmanager
def planted(fault: str | None):
    """Plant ``fault`` (or nothing, for None) for the ``with`` block."""
    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    import jax
    import repro.api.session as session_mod
    import repro.core.rounds as rounds_mod

    undo = []
    if fault == "unchanged":
        saved = _wrap_runners(session_mod,
                              lambda run: (lambda state, *a, **k: state))
        undo.append(lambda: [setattr(session_mod, n, f)
                             for n, f in saved.items()])
    elif fault == "altered":
        def wrap(run):
            def altered(state, *a, **k):
                out = run(state, *a, **k)
                out = dict(out)
                out["params"] = jax.tree.map(
                    lambda new, old: new + 0.5 * (new - old),
                    out["params"], state["params"])
                return out
            return altered
        saved = _wrap_runners(session_mod, wrap)
        undo.append(lambda: [setattr(session_mod, n, f)
                             for n, f in saved.items()])
    elif fault == "half_batch":
        orig = rounds_mod.xent_loss

        def half(model, params, xb, yb):
            h = xb.shape[0] // 2
            return orig(model, params, xb[:h], yb[:h])

        rounds_mod.xent_loss = half
        undo.append(lambda: setattr(rounds_mod, "xent_loss", orig))
    try:
        yield
    finally:
        for u in reversed(undo):
            u()
