"""Smoke run of the federated round on one TPU chip, at full model width.

Drives the public API (``ExperimentSpec`` → ``Session.from_spec(...).run()``)
on the paper's ResNet-18 with GroupNorm at width 64 (stages of 64/128/256/512
channels, 11,220,132 params) over CIFAR-100-shaped data generated from
``--seed`` (60,000 32×32×3 images in 100 classes, split 50,000/10,000), in
the cross-silo federation of the paper's Table I: N=8 clients, γ=0.5,
budgets p_i = (1/2)^⌊4i/8⌋, strategy ``cc``, K=5 local steps of batch 64.
All phases run in this one process, a few rounds each with one evaluation
at the end:

  (a) ``executor="scan"`` on the tree-ops path;
  (b) the same spec with ``use_fused=True`` (the fused Pallas kernel);
  (c) ``use_fused=True, compress="int8"`` (the int8-history kernel).

It fails (non-zero exit, no result line) unless JAX's first device is a
TPU, the compiled fused spans of (b) and (c) hold a ``tpu_custom_call``
(Mosaic lowered the kernel; no interpreter or jnp fallback ran), (a) and
(b) agree on the final params within ``PARAM_TOL``, and (c) is finite.

(a) and (b) run at ``"highest"`` f32 matmul precision. The kernel sums
the clients in another order than the tree ops, and the next rounds'
local SGD grows that last-bit difference. On a v5e it reached 2.0e-3 in
three rounds at the TPU's default precision (one bf16 pass per f32
matmul or convolution) and 7.2e-4 at ``"highest"``, against a largest
param move of 3.3e-2. (c) runs at the default precision, as users run.

``--chips 4`` runs only the multi-chip path and its reference: the same
spec under ``executor="sharded"``, the cohort of 8 split 2 per chip over
``make_client_mesh(4)``, against the one-device scan run (a), both at
``"highest"``, for one round unless ``--rounds`` says otherwise. The
sharded run trains its 2 clients per chip in a program of its own and
the cross-chip ``psum`` sums in another order; on four v5e chips SGD
grew those rounding differences to 1.4e-3 in three rounds, so the
comparison is taken before later rounds amplify them.

Timings below are smoke timings on the host clock, not metrics:
``compile_s`` is the span's ahead-of-time compile (or its load from the
persistent cache), ``run_s`` the session's run with the final
evaluation. The last line of stdout is one JSON object naming the
device.

Usage:
    python3 chip_smoke.py [--rounds 3] [--seed 0]
    python3 chip_smoke.py --chips 4 [--rounds 1]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

#: max |Δ| allowed between the final params of two executors of one spec
#: at "highest" precision. Local training is the same computation in
#: both; only the f32 summation order of the aggregate differs, and the
#: rounds after it start from params that differ in the last bits. A
#: wrong kernel is off by a share of the update itself, which each phase
#: prints as ``max_abs_param_move``.
PARAM_TOL = 1e-3


def smoke_spec(rounds: int, seed: int, **overrides):
    """The Table-I cross-silo spec at ResNet-18-GN's full width."""
    from repro.api import ExperimentSpec
    fields = dict(
        dataset="image", hw=32, channels=3, n_classes=100,
        n_samples=60_000, test_frac=1 / 6,
        n_clients=8, partition="gamma", gamma=0.5,
        budget="power", beta=4,
        model="resnet18", width=64,
        strategy="cc", local_steps=5, batch_size=64,
        rounds=rounds, eval_every=rounds, seed=seed)
    fields.update(overrides)
    return ExperimentSpec(**fields)


def _say(phase: str, **kw) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def run_phase(name: str, spec, *, lower_span: bool) -> dict:
    """Run one session to the end of its plan. With ``lower_span`` the
    scan executor's span program is first compiled ahead of time through
    its own factory, so its text can be searched for the kernel; the
    session then finds that program in the persistent compile cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import Session
    from repro.core.rounds import make_policy_span_runner

    t0 = time.perf_counter()
    sess = Session.from_spec(spec)
    out = {"build_s": time.perf_counter() - t0, "hlo": None,
           "compile_s": None}
    init = [np.asarray(x) for x in jax.tree.leaves(sess.state["params"])]
    if lower_span:
        run = make_policy_span_runner(sess.model, sess.data, sess.fed,
                                      sess.policy, sess.profile,
                                      fused=sess.use_fused)
        sel = jnp.asarray(sess.plan.selection)
        t0 = time.perf_counter()
        compiled = run.func.lower(sess.state, sel, sess.k_active,
                                  **run.keywords).compile()
        out["compile_s"] = time.perf_counter() - t0
        out["hlo"] = compiled.as_text()
    t0 = time.perf_counter()
    sess.run()
    jax.block_until_ready(sess.state["params"])
    out["run_s"] = time.perf_counter() - t0
    out["params"] = [np.asarray(x) for x in
                     jax.tree.leaves(sess.state["params"])]
    out["test_acc"] = sess.metrics.last("test_acc")
    out["n_params"] = sum(x.size for x in out["params"])
    moved = max(float(np.max(np.abs(x - y)))
                for x, y in zip(out["params"], init))
    _say(name, executor=spec.executor, use_fused=spec.use_fused,
         compress=spec.compress,
         precision=jax.config.jax_default_matmul_precision or "default",
         rounds=sess.t, n_params=out["n_params"],
         max_abs_param_move=f"{moved:.3e}",
         build_s=f"{out['build_s']:.2f}",
         compile_s=("n/a" if out["compile_s"] is None
                    else f"{out['compile_s']:.2f}"),
         run_s=f"{out['run_s']:.2f}",
         s_per_round_smoke=f"{out['run_s'] / sess.t:.3f}",
         test_acc=f"{out['test_acc']:.4f}")
    return out


def max_abs_diff(a: dict, b: dict) -> float:
    import numpy as np
    return max(float(np.max(np.abs(x - y)))
               for x, y in zip(a["params"], b["params"]))


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.
                                 RawDescriptionHelpFormatter)
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds per phase (default: 3, or 1 with "
                         "--chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded executor over four chips "
                         "against the one-device scan run")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX finds no TPU (first device: {dev.platform})")
    if len(jax.devices()) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX finds "
             f"{len(jax.devices())}")

    from repro.launch.compile_cache import enable_compile_cache
    _say("setup", device=dev.device_kind, count=len(jax.devices()),
         jax=jax.__version__, compile_cache=enable_compile_cache())

    rounds = args.rounds or (1 if args.chips == 4 else 3)
    spec = smoke_spec(rounds, args.seed)
    with jax.default_matmul_precision("highest"):
        a = run_phase("a:scan", spec, lower_span=True)
    if args.chips == 4:
        from repro.launch.mesh import best_client_shards
        if best_client_shards(spec.n_clients) != 4:
            fail("the 8-client cohort does not split over 4 chips")
        with jax.default_matmul_precision("highest"):
            s = run_phase("sharded", spec.replace(executor="sharded"),
                          lower_span=False)
        diff = max_abs_diff(a, s)
        _say("check", sharded_vs_scan_max_abs_diff=f"{diff:.3e}",
             tol=PARAM_TOL, clients_per_chip=spec.n_clients // 4)
        if not diff <= PARAM_TOL:
            fail(f"sharded and scan params differ by {diff:.3e} "
                 f"> {PARAM_TOL}")
    else:
        with jax.default_matmul_precision("highest"):
            b = run_phase("b:fused", spec.replace(use_fused=True),
                          lower_span=True)
        c = run_phase("c:fused-int8",
                      spec.replace(use_fused=True, compress="int8"),
                      lower_span=True)
        for name, ph in (("b", b), ("c", c)):
            if "tpu_custom_call" not in ph["hlo"]:
                fail(f"phase {name}: no tpu_custom_call in the compiled "
                     "fused span")
        diff = max_abs_diff(a, b)
        finite = all(bool(np.isfinite(x).all()) for x in c["params"])
        _say("check", tpu_custom_call_in_fused_spans=True,
             fused_vs_scan_max_abs_diff=f"{diff:.3e}", tol=PARAM_TOL,
             int8_params_finite=finite)
        if not diff <= PARAM_TOL:
            fail(f"fused and scan params differ by {diff:.3e} "
                 f"> {PARAM_TOL}")
        if not finite:
            fail("the int8 run has non-finite params")

    stats = dev.memory_stats() or {}
    _say("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                               "not reported"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
