"""Training launcher: any assigned architecture (reduced or full), any FT
policy, failure injection from the paper's models.

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --steps 50 \
      --policy hybrid --failures random --per-hour 2 [--full] [--json]

On this CPU container the default is the reduced config; --full uses the
exact assigned config (only sensible on a real pod — it will be slow).

Supervision contract (the orchestrator daemon and CI parse this, never
the human text): ``--json`` makes the final line a single JSON object
with the run's counters, and the exit code is typed per
``repro.orchestrator.contract`` — 0 ok, 42 fault-injected, 43 stalled,
44 preempted (this entrypoint exits 0 on success; the non-zero codes are
what a supervised run reports when killed through those paths).
"""
from __future__ import annotations

import argparse
import json
import shutil

import jax
import numpy as np

from repro.configs import all_archs, get_arch
from repro.core.failure import FailureModel
from repro.core.trainer import FTTrainer
from repro.data.synthetic import token_batches
from repro.models import build_model
from repro.train.step import make_train_step
from repro.utils.backend import use_compile_cache
from repro.utils.tree import tree_bytes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=sorted(all_archs()))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--policy", default="hybrid",
                    choices=["none", "checkpoint", "agent", "core", "hybrid"])
    ap.add_argument("--failures", default="none",
                    choices=["none", "periodic", "random"])
    ap.add_argument("--per-hour", type=int, default=1, dest="per_hour")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--hosts", type=int, default=4)
    ap.add_argument("--full", action="store_true", help="full assigned config")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="final line is one machine-readable JSON status object")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    train_step, init_state, *_ = make_train_step(model, lr=args.lr)
    make_batch = token_batches(seed=0, batch=args.batch, seq=args.seq, vocab=cfg.vocab)

    state0 = init_state(jax.random.key(0))
    print(f"{args.arch}{'' if args.full else ' (reduced)'}: "
          f"{tree_bytes(state0['params'])/4e6:.1f}M params, policy={args.policy}")

    failures = []
    if args.failures != "none":
        failures = FailureModel(
            kind=args.failures, n_nodes=args.hosts, horizon_s=float(args.steps),
            period_s=max(args.steps / max(args.per_hour, 1), 1.0),
            offset_s=args.steps * 0.25, seed=11,
        ).events()
        print(f"injected failures at steps: {[round(e.t,1) for e in failures]}")

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    trainer = FTTrainer(
        train_step, lambda: init_state(jax.random.key(0)), make_batch,
        policy=args.policy if args.policy != "none" else "checkpoint",
        n_hosts=args.hosts, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        async_ckpt=args.async_ckpt, seed=11,
    )
    rep = trainer.run(args.steps, failures=failures)
    if args.as_json:
        from repro.orchestrator.contract import EXIT_OK

        print(json.dumps({
            "status": "ok",
            "exit_code": EXIT_OK,
            "arch": args.arch,
            "policy": args.policy,
            "steps": rep.steps_run,
            "steps_reexecuted": rep.steps_reexecuted,
            "migrations": rep.migrations,
            "restores": rep.restores,
            "checkpoints": rep.checkpoints,
            "train_time_s": round(rep.train_time_s, 4),
            "ft_time_s": round(rep.ft_time_s, 4),
            "overhead_fraction": round(rep.overhead_fraction, 6),
        }))
    else:
        print(f"steps={rep.steps_run} reexec={rep.steps_reexecuted} "
              f"migrations={rep.migrations} restores={rep.restores} "
              f"checkpoints={rep.checkpoints}")
        print(f"train={rep.train_time_s:.2f}s ft={rep.ft_time_s:.3f}s "
              f"overhead={100*rep.overhead_fraction:.1f}%")


if __name__ == "__main__":
    main()
