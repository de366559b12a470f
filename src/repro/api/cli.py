"""``python -m repro`` — run federated experiments from spec files.

Subcommands:

* ``init SPEC.json [--set field=value ...]``
      write a (possibly overridden) default spec file to start from;
* ``run SPEC.json [--set field=value ...] [--ckpt-dir D] [--save-every N]``
      run one session from a spec, optionally checkpointing as it goes;
* ``resume CKPT_DIR [--rounds N]``
      continue an interrupted run purely from its checkpoint directory
      (the spec travels inside the checkpoint);
* ``sweep SPEC.json --grid field=v1,v2 [--grid ...]``
      expand the spec over grids and print a Table-I-style comparison.

Examples:
    python -m repro init /tmp/exp.json --set rounds=3 --set strategy=cc
    python -m repro run /tmp/exp.json --ckpt-dir /tmp/ckpt --save-every 10
    python -m repro resume /tmp/ckpt
    python -m repro sweep /tmp/exp.json --grid strategy=cc,s2,fedavg

Executor selection rides the spec fields: ``--set executor=sharded --set
cohort_size=8`` runs each round's sampled cohort shard_map'ed over the
client mesh (all visible devices), ``--set use_fused=true`` takes the
fused Pallas path. ``--compress int8`` (with ``--set use_fused=true``)
stores the Δ history as int8 payload + per-client scales and runs the
quantized fused kernel — ~4× less history memory/wire traffic.

Budget policies: ``--policy {precompiled,energy,deadline,adaptive}`` picks
the in-loop train/estimate decision maker and ``--device-profile
{budget,uniform}`` the simulated device runtime (shorthands for the spec
fields of the same names; fine-grained knobs ride ``--set``, e.g. ``--set
energy_capacity=2.0 --set load_mean=0.3 --set deadline=1.5``):

    python -m repro run exp.json --policy energy --set harvest_scale=0.8
    python -m repro sweep exp.json --grid policy=precompiled,energy,adaptive

Two-tier topologies: ``--topology {contiguous,striped}``, ``--edges E``
and ``--edge-period P`` (shorthands for the spec fields ``topology`` /
``n_edges`` / ``edge_period``) run the hierarchical client→edge→server
executor — pair them with ``--set executor=hierarchical``; per-edge
heterogeneity rides ``--set edge_speed=[1.0,0.5]``:

    python -m repro run exp.json --set executor=hierarchical \
        --topology contiguous --edges 4 --edge-period 5
    python -m repro sweep exp.json --set executor=hierarchical \
        --topology contiguous --edges 4 --grid edge_period=1,5,10

Asynchronous federation: ``--set executor=async`` runs the staleness-
tolerant buffered executor — clients deliver after a device-dependent
latency (``--set async_latency=2.0 --set async_jitter=0.5``) and the
server merges every K-th arrival (``--async-buffer K``) with staleness-
decayed weights (``--staleness-decay γ``, shape via ``--set
staleness_schedule=polynomial``). ``--history-store int8`` carries the
Δ history as the sharded quantized store (~25% of dense f32 at large P).
Zero latency with K=1 (the defaults) is bit-for-bit the scan executor:

    python -m repro run exp.json --set executor=async \
        --async-buffer 4 --staleness-decay 0.8 --set async_latency=2.0
    python -m repro sweep exp.json --set executor=async \
        --grid staleness_decay=0.5,0.8,1.0

Strategies and channels: ``--strategy`` picks the aggregation strategy
(choices generated from the registry, including the proximal ``fedprox``
with ``--set prox_mu=0.1`` and the dynamic-regularization ``feddyn`` with
``--set feddyn_alpha=0.1``); ``--channel aircomp`` uploads deltas over a
noisy over-the-air channel at ``--snr-db`` receive SNR, ``--set
channel_fading=true`` adds per-client Rayleigh gains:

    python -m repro run exp.json --strategy fedprox --set prox_mu=0.1
    python -m repro run exp.json --channel aircomp --snr-db 10 \
        --set channel_fading=true
    python -m repro sweep exp.json --channel aircomp --grid \
        channel_snr_db=0,10,20
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.api.callbacks import CheckpointCallback, VerboseLogger
from repro.api.session import Session
from repro.api.spec import ExperimentSpec
from repro.api.sweep import format_table, run_sweep
from repro.utils.logging import log


def _parse_value(text: str):
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        return text                       # bare strings need no quotes


def _parse_sets(pairs: list[str]) -> dict:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--set expects field=value, got {pair!r}")
        k, v = pair.split("=", 1)
        out[k] = _parse_value(v)
    return out


def _parse_grids(pairs: list[str]) -> dict:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--grid expects field=v1,v2,..., got {pair!r}")
        k, vs = pair.split("=", 1)
        out[k] = [_parse_value(v) for v in vs.split(",")]
    return out


def _load_spec(path: str, sets: list[str],
               policy: str | None = None,
               device_profile: str | None = None,
               topology: str | None = None,
               edges: int | None = None,
               edge_period: int | None = None,
               compress: str | None = None,
               async_buffer: int | None = None,
               staleness_decay: float | None = None,
               history_store: str | None = None,
               strategy: str | None = None,
               channel: str | None = None,
               snr_db: float | None = None,
               executor: str | None = None) -> ExperimentSpec:
    spec = ExperimentSpec.load(path)
    overrides = _parse_sets(sets)
    if policy:
        overrides["policy"] = policy
    if device_profile:
        overrides["device_profile"] = device_profile
    if topology:
        overrides["topology"] = topology
    if edges is not None:
        overrides["n_edges"] = edges
    if edge_period is not None:
        overrides["edge_period"] = edge_period
    if compress:
        overrides["compress"] = compress
    if async_buffer is not None:
        overrides["async_buffer"] = async_buffer
    if staleness_decay is not None:
        overrides["staleness_decay"] = staleness_decay
    if history_store:
        overrides["history_store"] = history_store
    if strategy:
        overrides["strategy"] = strategy
    if channel:
        overrides["channel"] = channel
    if snr_db is not None:
        overrides["channel_snr_db"] = snr_db
    if executor:
        overrides["executor"] = executor
    return spec.replace(**overrides) if overrides else spec


def _dump(obj: dict, path: str | None) -> None:
    if path:
        with open(path, "w") as f:
            json.dump(obj, f, indent=2)
        log(f"wrote {path}")


def cmd_init(args) -> int:
    # from_dict rather than the constructor: typo'd --set fields get the
    # "unknown spec fields" error instead of a raw TypeError
    spec = ExperimentSpec.from_dict(_parse_sets(args.set))
    spec.save(args.spec)
    log(f"wrote spec {args.spec}", strategy=spec.strategy,
        rounds=spec.rounds)
    return 0


def cmd_run(args) -> int:
    spec = _load_spec(args.spec, args.set, policy=args.policy,
                      device_profile=args.device_profile,
                      topology=args.topology, edges=args.edges,
                      edge_period=args.edge_period, compress=args.compress,
                      async_buffer=args.async_buffer,
                      staleness_decay=args.staleness_decay,
                      history_store=args.history_store,
                      strategy=args.strategy, channel=args.channel,
                      snr_db=args.snr_db, executor=args.executor)
    callbacks = [] if args.quiet else [VerboseLogger()]
    if args.save_every and not args.ckpt_dir:
        raise SystemExit("--save-every needs --ckpt-dir (nowhere to save)")
    if args.save_every:
        callbacks.append(CheckpointCallback(args.save_every))
    sess = Session.from_spec(spec, callbacks=callbacks,
                             ckpt_dir=args.ckpt_dir or None)
    sess.run()
    if args.ckpt_dir:
        sess.save()
    rep = sess.cost_report()
    log("run done", **{k: f"{v:.4f}" if isinstance(v, float) else v
                       for k, v in sess.summary().items()})
    out = {"spec": spec.to_dict(), "summary": sess.summary(),
           "metrics": sess.metrics.history, "cost": rep}
    _dump(out, args.out)
    print(json.dumps(sess.summary()))
    return 0


def cmd_resume(args) -> int:
    callbacks = [] if args.quiet else [VerboseLogger()]
    sess = Session.restore_from(args.ckpt_dir, callbacks=callbacks)
    log(f"resumed at round {sess.t}/{sess.plan.rounds}",
        strategy=sess.fed.strategy)
    sess.run(args.rounds)
    sess.save()
    out = {"summary": sess.summary(), "metrics": sess.metrics.history}
    _dump(out, args.out)
    print(json.dumps(sess.summary()))
    return 0


def cmd_sweep(args) -> int:
    spec = _load_spec(args.spec, args.set, policy=args.policy,
                      device_profile=args.device_profile,
                      topology=args.topology, edges=args.edges,
                      edge_period=args.edge_period, compress=args.compress,
                      async_buffer=args.async_buffer,
                      staleness_decay=args.staleness_decay,
                      history_store=args.history_store,
                      strategy=args.strategy, channel=args.channel,
                      snr_db=args.snr_db, executor=args.executor)
    grid = _parse_grids(args.grid)
    result = run_sweep(spec, grid, verbose=not args.quiet)
    _dump(result, args.out)
    print(format_table(result))
    return 0


def _add_policy_flags(p: argparse.ArgumentParser) -> None:
    # every choices= below is derived from the owning registry — a newly
    # registered strategy/executor/kind is reachable from the CLI without
    # touching this file (pinned by tests/test_cli_registries.py)
    from repro.core.budget import POLICY_KINDS
    from repro.core.channel import CHANNEL_KINDS
    from repro.core.hierarchy import TOPOLOGY_KINDS
    from repro.core.history_store import STORE_KINDS
    from repro.core.rounds import COMPRESS_KINDS, EXECUTORS
    from repro.core.strategies import available_strategies
    from repro.system.devices import PROFILE_KINDS
    p.add_argument("--strategy", default=None,
                   choices=available_strategies(),
                   help="aggregation strategy (shorthand for --set "
                        "strategy=...; choices come from the registry)")
    p.add_argument("--executor", default=None, choices=EXECUTORS,
                   help="round executor (shorthand for --set "
                        "executor=...)")
    p.add_argument("--channel", default=None, choices=CHANNEL_KINDS,
                   help="uplink channel model (shorthand for --set "
                        "channel=...; aircomp adds AWGN at --snr-db)")
    p.add_argument("--snr-db", type=float, default=None,
                   help="aircomp receive SNR in dB (shorthand for --set "
                        "channel_snr_db=...; needs --channel aircomp)")
    p.add_argument("--policy", default=None, choices=POLICY_KINDS,
                   help="budget policy (shorthand for --set policy=...)")
    p.add_argument("--device-profile", default=None,
                   choices=PROFILE_KINDS,
                   help="device runtime (shorthand for --set "
                        "device_profile=...)")
    p.add_argument("--topology", default=None, choices=TOPOLOGY_KINDS,
                   help="two-tier client→edge assignment (shorthand for "
                        "--set topology=...; needs "
                        "--set executor=hierarchical)")
    p.add_argument("--edges", type=int, default=None,
                   help="edge aggregator count (shorthand for "
                        "--set n_edges=...)")
    p.add_argument("--edge-period", type=int, default=None,
                   help="intra-edge rounds per server sync (shorthand "
                        "for --set edge_period=...)")
    p.add_argument("--compress", default=None, choices=COMPRESS_KINDS,
                   help="Δ-history wire/memory format (shorthand for "
                        "--set compress=...; int8 needs "
                        "--set use_fused=true)")
    p.add_argument("--async-buffer", type=int, default=None,
                   help="merge every K-th arrival (shorthand for --set "
                        "async_buffer=...; needs --set executor=async)")
    p.add_argument("--staleness-decay", type=float, default=None,
                   help="γ of the staleness merge weight w(s) (shorthand "
                        "for --set staleness_decay=...)")
    p.add_argument("--history-store", default=None,
                   choices=STORE_KINDS,
                   help="async Δ-history carry layout (shorthand for "
                        "--set history_store=...)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro",
                                 description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("init", help="write a default spec file")
    p.add_argument("spec")
    p.add_argument("--set", action="append", default=[],
                   metavar="FIELD=VALUE")
    p.set_defaults(fn=cmd_init)

    p = sub.add_parser("run", help="run one session from a spec")
    p.add_argument("spec")
    p.add_argument("--set", action="append", default=[],
                   metavar="FIELD=VALUE")
    _add_policy_flags(p)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--save-every", type=int, default=0,
                   help="checkpoint every N rounds (with --ckpt-dir)")
    p.add_argument("--out", default="", help="write metrics JSON here")
    p.add_argument("--quiet", action="store_true")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("resume", help="continue from a checkpoint dir")
    p.add_argument("ckpt_dir")
    p.add_argument("--rounds", type=int, default=None,
                   help="how many more rounds (default: finish the plan)")
    p.add_argument("--out", default="")
    p.add_argument("--quiet", action="store_true")
    p.set_defaults(fn=cmd_resume)

    p = sub.add_parser("sweep", help="grid-expand a spec and compare")
    p.add_argument("spec")
    p.add_argument("--set", action="append", default=[],
                   metavar="FIELD=VALUE")
    _add_policy_flags(p)
    p.add_argument("--grid", action="append", default=[], required=True,
                   metavar="FIELD=V1,V2")
    p.add_argument("--out", default="")
    p.add_argument("--quiet", action="store_true")
    p.set_defaults(fn=cmd_sweep)
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
