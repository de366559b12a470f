"""One run of one cell: set-up, the measured window, a traced window, the check.

The path a run drives is the program's public one: a
:class:`repro.api.session.Session` over the cell's model, federation and
schedule, advanced by ``Session.run(n_rounds=eval_every)``, which runs one
``lax.scan`` span of ``eval_every`` rounds through the cell's span runner
and then the evaluation on its cadence. The benchmark makes every input
(``bench/generate.py``) and hands the program only those; the cell's model
family (``bench/families/<family>.py``) makes the data and weights and
builds the program's model.

Each call dispatches its rounds as ``lax.scan`` spans of the mix's
``span_rounds`` (a callback's ``sync_every``).

Set-up (``setup_s``, from the start of the process to the start of the
window) builds the session, places the benchmark's weights and round key
in its state, and drives the first ``eval_every`` rounds and their
evaluation, which loads or compiles every program the window uses. The
state after the cell's first ``rounds`` (``bench/limits/<cell>.json``)
is copied to the host for the check; that copy is not counted as set-up.

The window repeats ``Session.run(n_rounds=eval_every)`` until
``--seconds`` have passed, each call ending in ``block_until_ready``. A
callback splits each call into the benchmark's host spans: ``span``
(the rounds, up to their end on the device), ``eval`` and ``sync``.
The program's counters (``Session.counters``) are read just before the
window and just after it, outside its time. With ``--trace 1`` the run
measures the same window, then profiles two more calls in a window of
their own for the device metrics; the trace is read with the program's
``fed.*`` scopes and host spans (``bench/trace_scopes.py``).
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from bench import check, generate, trace_reduce, trace_scopes
from bench.cells import Cell, family, reader
from bench.faults import planted

TRACED_CALLS = 2


def say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """The run cannot be measured here: no accelerator, too few chips, or
    a device without an entry in the peaks table."""


@dataclass
class RunRecord:
    """What a run measured, as the metric readers see it."""
    config: dict
    traffic: dict
    chips: int
    peaks: dict | None
    window_s: float = 0.0
    rounds: int = 0
    client_rounds: int = 0
    trained_client_rounds: int = 0
    evals: int = 0
    spans: list = field(default_factory=list)     # (name, seconds)
    compiles: int = 0
    counters: dict = field(default_factory=dict)  # their change in window
    family: object = None                         # the model family module
    trace: trace_scopes.ScopedTrace | None = None
    traced_rounds: int = 0
    devices: list = field(default_factory=list)   # trace device ids


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, from
    ``jax.monitoring`` events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1


class SpanClock:
    """The benchmark's host spans, as a ``Session`` callback.

    ``begin(name)`` closes the open span and opens the next; with
    ``annotate`` each span is also a ``TraceAnnotation`` named
    ``bench.<name>`` in the profiler's trace. Its ``sync_every`` makes
    ``Session.run`` dispatch one ``lax.scan`` span of ``span_rounds``
    rounds at a time (the mix's ``span_rounds``), as any callback with a
    per-round cadence does; the host waits for the device only before an
    evaluation."""

    needs_python_loop = False

    def __init__(self, eval_every: int, span_rounds: int):
        self.eval_every = eval_every
        self.sync_every = span_rounds if span_rounds < eval_every else None
        self.marks: list[tuple[str, float, float]] = []
        self.annotate = False
        self._open = None
        self._ann = None

    def begin(self, name: str) -> None:
        self.finish()
        self._open = (name, time.perf_counter())
        if self.annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation("bench." + name)
            self._ann.__enter__()

    def finish(self) -> None:
        if self._open is not None:
            name, t0 = self._open
            self.marks.append((name, t0, time.perf_counter()))
            self._open = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def on_round_end(self, session, t):
        if t % self.eval_every == 0:        # an evaluation comes next
            import jax
            jax.block_until_ready(session.state)
            self.begin("eval")

    def on_eval(self, session, t, acc):
        self.begin("sync")

    def on_checkpoint(self, session, t, path):
        pass


def check_devices(chips: int, peaks_table=None):
    """The devices the cell runs on; raises :class:`NoChip` where JAX
    finds no TPU, fewer chips than the cell asks for, or a kind with no
    peak rates."""
    import jax
    from bench.peaks import PEAKS
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    kind = devs[0].device_kind
    table = PEAKS if peaks_table is None else peaks_table
    if kind not in table:
        raise NoChip(f"no peak rates for device kind {kind!r}")
    return devs[:chips], table[kind]


def enable_cache() -> str:
    """JAX's persistent compile cache at the program's fixed path inside
    the checkout (or ``JAX_COMPILATION_CACHE_DIR``), holding every
    program however fast it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def precision(config: dict):
    import jax
    p = config["execution"].get("matmul_precision", "default")
    return (contextlib.nullcontext() if p == "default"
            else jax.default_matmul_precision(p))


def build_session(cell: Cell, inputs: generate.Inputs, clock):
    """The program's ``Session`` for the cell, seeded with the benchmark's
    weights and round key."""
    import jax
    from repro.api.session import Session
    from repro.core.rounds import FedConfig
    from repro.core.schedules import Plan
    from repro.data.federated import FederatedData

    cfg, traffic = cell.config, cell.traffic
    tr, ex = cfg["training"], cfg["execution"]
    if traffic.get("participation", 1.0) != 1.0:
        raise ValueError("the generator makes full participation only")
    model = family(cell).build_model(cfg)
    fed = FedConfig(strategy=tr["strategy"], variant=tr["variant"],
                    local_steps=tr["local_steps"],
                    batch_size=tr["batch_size"], lr=tr["lr"],
                    compress=ex["compress"], seed=0)
    plan = Plan(selection=inputs.selection, training=inputs.training,
                p=inputs.budgets)
    data = FederatedData(inputs.x, inputs.y, inputs.sizes,
                         cfg["model"]["n_classes"])
    sess = Session(model, data, fed, plan, x_test=inputs.x_test,
                   y_test=inputs.y_test, eval_every=traffic["eval_every"],
                   executor=ex["executor"], use_fused=ex["use_fused"],
                   callbacks=[clock])
    # the program's own initial weights and key give way to the
    # benchmark's, so that the reference starts from the same point
    st = dict(sess.state)
    st["params"] = inputs.params
    if "prev_local" in st:
        n = inputs.sizes.shape[0]
        st["prev_local"] = jax.tree.map(
            lambda p: jax.numpy.broadcast_to(p, (n,) + p.shape),
            inputs.params)
    st["key"] = inputs.key
    sess.state = st
    return sess


def capture(sess) -> dict:
    """The state the rounds run so far leave, copied to the host."""
    import jax
    st = jax.device_get({k: sess.state[k] for k in ("params", "deltas")})
    return {"params": st["params"],
            "history": check.history_rows(st["deltas"], st["params"]),
            "trained": sess.ledger()["train_rounds"]}


def drive(sess, clock, span: int, seconds: float) -> tuple[int, float]:
    """Calls of ``Session.run(n_rounds=span)``, each up to the end of its
    work on the device, until ``seconds`` have passed; returns (rounds
    run, seconds taken)."""
    import jax
    rounds, t0 = 0, time.perf_counter()
    while True:
        if sess.t + span > sess.plan.rounds:
            say(f"the plan's {sess.plan.rounds} rounds are used up")
            break
        clock.begin("span")
        sess.run(n_rounds=span)
        jax.block_until_ready(sess.state)
        clock.finish()
        rounds += span
        if time.perf_counter() - t0 >= seconds:
            break
    return rounds, time.perf_counter() - t0


def _trained_in(inputs: generate.Inputs, start: int, stop: int) -> int:
    return int((inputs.selection[start:stop]
                & inputs.training[start:stop]).sum())


def traced_window(sess, clock, span: int, keep_dir: str | None):
    """Profile ``TRACED_CALLS`` calls; returns (trace, rounds traced)."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        clock.annotate = True
        jax.profiler.start_trace(tmp)
        rounds, _ = drive(sess, clock, span, 0.0)
        for _ in range(TRACED_CALLS - 1):
            more, _ = drive(sess, clock, span, 0.0)
            rounds += more
        jax.profiler.stop_trace()
        clock.annotate = False
        path = trace_reduce.find_xplane(tmp)
        tr = trace_scopes.read_xplane(path)
        if keep_dir:
            os.makedirs(keep_dir, exist_ok=True)
            tr.save(os.path.join(keep_dir, "trace.json.gz"))
        return tr, rounds
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def make_clock(cell: Cell) -> SpanClock:
    return SpanClock(int(cell.traffic["eval_every"]),
                     int(cell.traffic["span_rounds"]))


def prepare(cell: Cell, seed: int, clock):
    """Inputs from the seed and the session over them."""
    import jax
    inputs = generate.make_inputs(family(cell), cell.config, cell.traffic,
                                  seed)
    jax.block_until_ready((inputs.x, inputs.params))
    return inputs, build_session(cell, inputs, clock)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, use_cache: bool = True,
        peaks: dict | None = None, fault: str | None = None,
        keep_trace: str | None = None) -> dict:
    """One run of ``cell``; returns the result object the run prints."""
    import jax

    if require_chip:
        devices, chip = check_devices(cell.chips)
    else:
        devices, chip = jax.devices()[:cell.chips], peaks
    if use_cache:
        say("compile cache:", enable_cache())
    compiles = CompileCounter()
    span = int(cell.traffic["eval_every"])
    checked = int(cell.limits["rounds"])
    n_sel = int(cell.config["federation"]["n_clients"])
    clock = make_clock(cell)
    with planted(fault), precision(cell.config):
        inputs, sess = prepare(cell, seed, clock)
        # the rounds the check compares, through the window's own programs
        drive(sess, clock, checked, 0.0)
        # the copy for the check is not set-up; the rounds before it are
        t_cap = time.perf_counter()
        got = capture(sess)
        t_cap = time.perf_counter() - t_cap
        # the rest of the first call's rounds and its evaluation, which
        # warm every program the window runs
        if span > checked:
            drive(sess, clock, span - checked, 0.0)
        setup_s = time.perf_counter() - t_start - t_cap
        say(f"set-up {setup_s:.3f} s; window of {seconds} s")

        clock.marks.clear()
        counted = sess.counters
        before, start = compiles.count, sess.t
        rounds, window_s = drive(sess, clock, span, seconds)
        compiled = compiles.count - before
        rec = RunRecord(config=cell.config, traffic=cell.traffic,
                        chips=cell.chips, peaks=chip, window_s=window_s,
                        rounds=rounds, client_rounds=rounds * n_sel,
                        trained_client_rounds=_trained_in(
                            inputs, start, start + rounds),
                        evals=rounds // span,
                        spans=[(n, b - a) for n, a, b in clock.marks],
                        compiles=compiled,
                        counters={k: v - counted[k]
                                  for k, v in sess.counters.items()},
                        family=family(cell))
        say(f"window: {rounds} rounds in {window_s:.4f} s, "
            f"{rec.compiles} compiles")
        finite = all(bool(np.isfinite(np.asarray(l)).all())
                     for l in jax.tree.leaves(sess.state["params"]))
        if trace:
            clock.marks.clear()
            rec.trace, rec.traced_rounds = traced_window(
                sess, clock, span, keep_trace)
            rec.devices = sorted(d.id for d in devices)
            if keep_trace:
                with open(os.path.join(keep_trace, "record.json"), "w") as f:
                    json.dump({k: v for k, v in vars(rec).items()
                               if k not in ("trace", "family")}, f)
        mem = memory_peak(devices)

    del sess
    gc.collect()
    ref = check.reference_outputs(cell, inputs)
    values = check.numbers(inputs.params, got, ref)
    correct, compared = check.verdict(values, cell.limits)
    correct = correct and finite

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"], cell.root)(rec)
            if v is None:
                # the cell lists the metric, so its reader should have
                # found something: the names it matches have gone stale
                say(f"ERROR: metric {m['name']} found nothing to read in "
                    f"{cell.name}, which lists it")
            else:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = {"client_rounds_per_s": rec.client_rounds / rec.window_s,
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    if trace:
        ids = rec.devices
        device["busy_s"] = float(np.mean(
            [trace_reduce.busy_s(rec.trace, d) for d in ids]))
        device["window_s"] = trace_reduce.window_s(rec.trace)
    out = {"correct": bool(correct), "attempted": rec.client_rounds,
           "failed": 0 if finite else rec.client_rounds,
           "metrics": metrics, "device": device}
    if trace:
        d0 = rec.devices[0]
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in
                           trace_reduce.top_ops(rec.trace, d0)],
            "idle_gaps": [[n, s] for n, s in
                          trace_reduce.idle_gaps(rec.trace, d0)[:10]]}
    out["checks"] = compared
    return out
