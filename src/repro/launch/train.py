"""Training launcher.

Two modes sharing the same configs and model zoo:

* ``--mode centralized`` — plain data+tensor-parallel LM training of any
  assigned architecture (reduced or full) on the available mesh.
* ``--mode federated``   — CC-FedAvg over the paper's experiment models
  (MLP/CNN/ResNet on synthetic data), the end-to-end driver used by the
  examples and benchmarks.

On this CPU container use ``--reduced`` (the dry-run exercises the full
configs; see launch/dryrun.py).

Examples:
    python -m repro.launch.train --mode centralized --arch qwen3-1.7b \
        --reduced --steps 20 --batch 4 --seq 128
    python -m repro.launch.train --mode federated --strategy cc \
        --clients 8 --rounds 100 --beta 4 --gamma 0.5
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.api import ExperimentSpec, Session, VerboseLogger
from repro.checkpoint.store import CheckpointManager
from repro.data.synthetic import token_lm_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models.steps import init_train_state, make_train_step
from repro.optim.optimizers import make_optimizer
from repro.optim.schedules import warmup_cosine_lr
from repro.utils.logging import log


def run_centralized(args) -> dict:
    cfg = cfglib.get_config(args.arch, reduced=args.reduced)
    opt = make_optimizer(args.optimizer)
    lr = warmup_cosine_lr(args.lr, max(1, args.steps // 10), args.steps)
    rng = jax.random.PRNGKey(args.seed)
    state = init_train_state(rng, cfg, opt)
    step = jax.jit(make_train_step(cfg, opt, lr))
    data = token_lm_dataset(np.random.default_rng(args.seed),
                            n_seq=max(64, args.batch * 4),
                            seq_len=args.seq, vocab=cfg.vocab)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2) if args.ckpt_dir else None
    t0 = time.time()
    losses = []
    for i in range(args.steps):
        idx = np.random.default_rng(i).integers(0, len(data), args.batch)
        batch = {"tokens": jnp.asarray(data.x[idx])}
        if cfg.n_codebooks:
            batch["tokens"] = jnp.broadcast_to(
                batch["tokens"][:, None],
                (args.batch, cfg.n_codebooks, args.seq))
        if cfg.n_vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.n_vision_tokens, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
            batch["pos3"] = jnp.broadcast_to(
                jnp.arange(args.seq, dtype=jnp.int32), (3, args.batch,
                                                        args.seq))
        elif cfg.mrope_sections:
            batch["pos3"] = jnp.broadcast_to(
                jnp.arange(args.seq, dtype=jnp.int32), (3, args.batch,
                                                        args.seq))
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        if (i + 1) % max(1, args.steps // 10) == 0:
            log(f"step {i + 1}/{args.steps}", loss=f"{losses[-1]:.4f}",
                lr=f"{float(metrics['lr']):.2e}")
            if ckpt:
                ckpt.save(i + 1, state)
    dt = time.time() - t0
    log("centralized done", arch=args.arch,
        loss0=f"{losses[0]:.4f}", lossN=f"{losses[-1]:.4f}",
        s_per_step=f"{dt / max(1, args.steps):.2f}")
    return {"losses": losses}


def federated_spec(args) -> ExperimentSpec:
    """Map the federated CLI flags onto one declarative spec."""
    return ExperimentSpec(
        dataset=args.dataset, n_samples=args.n_samples, dim=args.dim,
        n_classes=args.classes, n_clients=args.clients,
        partition="gamma", gamma=args.gamma,
        budget="power", beta=args.beta,
        model=args.model, width=args.width,
        strategy=args.strategy, variant=args.variant,
        local_steps=args.local_steps, batch_size=args.batch, lr=args.lr,
        schedule=args.schedule, rounds=args.rounds,
        participation=args.participation, eval_every=args.eval_every,
        seed=args.seed)


def run_federated_mode(args) -> dict:
    spec = federated_spec(args)
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir (nowhere to restore "
                         "from)")
    session = Session.from_spec(spec, callbacks=[VerboseLogger()],
                                ckpt_dir=args.ckpt_dir or None)
    if args.ckpt_dir and args.resume:
        session.restore()
        log(f"resumed at round {session.t}/{spec.rounds}")
    session.run()
    if args.ckpt_dir:
        session.save()
    rep = session.cost_report()
    log("federated done", strategy=args.strategy,
        acc=f"{session.metrics.last('test_acc'):.4f}",
        compute_saved=f"{rep['compute_saved_frac']:.1%}",
        upload_mb=f"{rep['upload_bytes'] / 1e6:.1f}")
    return {"acc": session.metrics.last("test_acc"), "cost": rep}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("centralized", "federated"),
                    default="federated")
    ap.add_argument("--seed", type=int, default=0)
    # centralized
    ap.add_argument("--arch", choices=cfglib.ARCH_NAMES,
                    default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--ckpt-dir", default="")
    # federated
    ap.add_argument("--strategy", default="cc")
    ap.add_argument("--variant", default="client",
                    choices=("client", "server", "mixed"))
    ap.add_argument("--schedule", default="adhoc",
                    choices=("adhoc", "round_robin", "sync", "dropout",
                             "full"))
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--beta", type=int, default=4)
    ap.add_argument("--gamma", type=float, default=0.5)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--dataset", default="gaussian",
                    choices=("gaussian", "teacher", "image"))
    ap.add_argument("--model", default="mlp",
                    choices=("mlp", "cnn", "resnet18"))
    ap.add_argument("--n-samples", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--resume", action="store_true",
                    help="federated: restore the latest checkpoint in "
                         "--ckpt-dir before running")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.mode == "centralized":
        run_centralized(args)
    else:
        run_federated_mode(args)


if __name__ == "__main__":
    main()
