"""The supervised worker: runs one shard of real work under the daemon.

This is the compute-side half of the shepherd pair (SNIPPETS.md
snippet 1): a thin wrapper that executes a registered workload's *real*
step function, drops a heartbeat + step-latency sample into the spool
after every step, checkpoints its migratable state, and dies with the
typed exit contract (:mod:`repro.orchestrator.contract`).

Pacing makes live and simulated timelines commensurable. The daemon
plans in *scaled time*: each step represents ``step_sim_s`` simulated
seconds and is paced to ``step_wall_s`` wall seconds (sleeping off any
surplus), so a shard's wall duration maps linearly onto the simulator's
horizon and a mid-run kill loses real, re-doable work. A ``slow``
command multiplies the pace (the straggler failure mode); probe
overhead the strategy would bill is folded into ``step_wall_s`` by the
planner, not re-modelled here.

Step programs bind workload names to runnable shards:

``analytic``       numpy matmul loop (light spawn; the CI smoke lane)
``genome_search``  one search sub-job of :class:`~repro.data.genome.GenomeSearchJob`
                   (real jax pattern matching; the paper's validation job)
``train_llm``      toy jax MLP train step (jit grad descent)

jax imports are lazy so analytic workers spawn in milliseconds.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np

from repro.orchestrator import contract
from repro.orchestrator.spool import Spool

#: default checkpoint cadence (steps) when an assignment doesn't set one
DEFAULT_CKPT_EVERY_STEPS = 2


# ----------------------------------------------------------- step programs ---
class StepProgram:
    """One runnable shard: a real step function plus serialisable state."""

    def step(self) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def state_dict(self) -> Dict:  # pragma: no cover - interface
        raise NotImplementedError

    def load_state(self, state: Dict) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def result(self) -> Dict:
        return {}


class AnalyticProgram(StepProgram):
    """Numpy matmul accumulation — cheap, deterministic, no jax import."""

    def __init__(self, seed: int, shard: int, size: int = 96):
        rng = np.random.default_rng(seed * 1000 + shard)
        self.a = rng.standard_normal((size, size))
        self.acc = np.zeros((size, size))
        self.steps_done = 0

    def step(self) -> None:
        self.acc = self.acc + self.a @ self.a.T
        self.steps_done += 1

    def state_dict(self) -> Dict:
        return {"steps_done": self.steps_done, "trace_sum": float(np.trace(self.acc))}

    def load_state(self, state: Dict) -> None:
        self.steps_done = int(state["steps_done"])
        self.acc = self.steps_done * (self.a @ self.a.T)  # state is replayable

    def result(self) -> Dict:
        return {"trace_sum": float(np.trace(self.acc)), "steps_done": self.steps_done}


class GenomeProgram(StepProgram):
    """One search sub-job of the paper's genome job; a step is one chunk.

    ``job`` is the shared :class:`~repro.data.genome.GenomeSearchJob`; the
    sub-job's cursor and partial hits are the state that migrates."""

    def __init__(self, job, shard: int):
        self.job = job
        self.state = {"node": shard, "cursor": 0, "hits": []}

    def step(self) -> None:
        self.job.run_sub_job_step(self.state)

    def state_dict(self) -> Dict:
        return {
            "node": self.state["node"],
            "cursor": self.state["cursor"],
            "hits": [list(h) for h in self.state["hits"]],
        }

    def load_state(self, state: Dict) -> None:
        self.state = {
            "node": int(state["node"]),
            "cursor": int(state["cursor"]),
            "hits": [tuple(h) for h in state["hits"]],
        }

    def result(self) -> Dict:
        return {"hits": [list(h) for h in sorted(set(map(tuple, self.state["hits"])))]}


class TrainProgram(StepProgram):
    """Toy jax MLP train step: jit'd gradient descent on a fixed batch."""

    def __init__(self, seed: int, shard: int, width: int = 32):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(seed * 1000 + shard)
        self.w1 = jnp.asarray(rng.standard_normal((width, width)) * 0.1)
        self.w2 = jnp.asarray(rng.standard_normal((width, 1)) * 0.1)
        self.x = jnp.asarray(rng.standard_normal((64, width)))
        self.y = jnp.asarray(rng.standard_normal((64, 1)))
        self.steps_done = 0

        def loss(w1, w2, x, y):
            h = jnp.tanh(x @ w1)
            return jnp.mean((h @ w2 - y) ** 2)

        self._grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
        self._loss = jax.jit(loss)

    def step(self) -> None:
        g1, g2 = self._grad(self.w1, self.w2, self.x, self.y)
        self.w1 = self.w1 - 0.05 * g1
        self.w2 = self.w2 - 0.05 * g2
        self.steps_done += 1

    def state_dict(self) -> Dict:
        return {
            "steps_done": self.steps_done,
            "w1": np.asarray(self.w1).tolist(),
            "w2": np.asarray(self.w2).tolist(),
        }

    def load_state(self, state: Dict) -> None:
        import jax.numpy as jnp

        self.steps_done = int(state["steps_done"])
        self.w1 = jnp.asarray(np.array(state["w1"]))
        self.w2 = jnp.asarray(np.array(state["w2"]))

    def result(self) -> Dict:
        loss = float(self._loss(self.w1, self.w2, self.x, self.y))
        return {"loss": loss, "steps_done": self.steps_done}


def _genome_program(seed: int, n_shards: int, n_steps: int, shard: int) -> GenomeProgram:
    from repro.data.genome import GenomeSearchJob, make_genome

    # every worker rebuilds the same job deterministically from the seed,
    # so a migrated shard resumes on identical data
    genome, patterns, _ = make_genome(
        length=2048 * n_shards * n_steps, n_patterns=6, seed=seed
    )
    job = GenomeSearchJob(genome, patterns, n_search=n_shards, chunks_per_node=n_steps)
    return GenomeProgram(job, shard)


def _train_program(seed: int, n_shards: int, n_steps: int, shard: int) -> TrainProgram:
    return TrainProgram(seed, shard)


def _analytic_program(seed: int, n_shards: int, n_steps: int, shard: int) -> AnalyticProgram:
    return AnalyticProgram(seed, shard)


#: workload name -> (shard-program factory, whether its step runs JAX and
#: so needs a device of its own in every live worker)
_PROGRAMS = {
    "analytic": (_analytic_program, False),
    "genome_search": (_genome_program, True),
    "train_llm": (_train_program, True),
    "train": (_train_program, True),
}

JAX_WORKLOADS = frozenset(name for name, (_, uses_jax) in _PROGRAMS.items() if uses_jax)


def make_program(workload: str, seed: int, n_shards: int, n_steps: int, shard: int) -> StepProgram:
    """Bind a workload name to a runnable shard program."""
    if workload not in _PROGRAMS:
        raise KeyError(
            f"no step program bound for workload {workload!r}; "
            "have ['analytic', 'genome_search', 'train_llm']"
        )
    factory, _ = _PROGRAMS[workload]
    return factory(seed, n_shards, n_steps, shard)


# -------------------------------------------------------------- worker loop ---
class Worker:
    """The supervised loop: poll commands, run paced steps, heartbeat."""

    def __init__(
        self,
        spool: Spool,
        wid: int,
        workload: str,
        seed: int,
        *,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        idle_poll_wall_s: float = 0.02,
        abort_after_s: Optional[float] = None,
    ):
        self.spool = spool
        self.wid = int(wid)
        self.workload = workload
        self.seed = int(seed)
        self.clock = clock
        self.sleep = sleep
        self.idle_poll_wall_s = idle_poll_wall_s
        self.abort_after_s = abort_after_s  # self-watchdog: exceed it -> EXIT_STALLED
        self.last_seq = -1
        self.program: Optional[StepProgram] = None
        self.shard: Optional[int] = None
        self.step = 0
        self.n_steps = 0
        self.step_wall_s = 0.0
        self.ckpt_every_steps = DEFAULT_CKPT_EVERY_STEPS
        self.slow_factor = 1.0
        self.done = False
        self.warmed = False

    # ------------------------------------------------------------ spool IO ---
    def _heartbeat(
        self,
        state: str,
        step_latency_s: Optional[float] = None,
        compute_s: Optional[float] = None,
    ) -> None:
        self.spool.write_heartbeat(
            self.wid,
            {
                "t_wall_s": self.clock(),
                "pid": os.getpid(),
                "state": state,
                "shard": self.shard,
                "step": self.step,
                "n_steps": self.n_steps,
                "step_latency_s": step_latency_s,
                "compute_s": compute_s,
                "slow_factor": self.slow_factor,
                "warmed": self.warmed,
            },
        )

    def _checkpoint(self) -> None:
        if self.program is None or self.shard is None:
            return
        self.spool.write_checkpoint(
            self.shard,
            {"shard": self.shard, "step": self.step, "state": self.program.state_dict()},
        )

    def _exit(self, code: int) -> int:
        self.spool.write_final(
            self.wid,
            {"code": code, "cause": contract.EXIT_NAMES.get(code, "crashed"),
             "shard": self.shard, "step": self.step},
        )
        return code

    # ------------------------------------------------------------ commands ---
    def _apply_command(self, cmd: Dict) -> Optional[int]:
        """Returns an exit code when the command terminates the worker."""
        op = cmd.get("op")
        if op == "die":
            return self._exit(contract.EXIT_FAULT_INJECTED)
        if op == "stop":
            self._checkpoint()
            return self._exit(contract.EXIT_PREEMPTED)
        if op == "slow":
            self.slow_factor = float(cmd.get("factor", 2.0))
        elif op == "checkpoint":
            self._checkpoint()
        elif op == "warm":
            # compile the workload's jit kernels on a throwaway program so a
            # later migration resumes at full pace (warm-spare contract)
            prog = make_program(
                self.workload, self.seed,
                int(cmd.get("n_shards", 1)), int(cmd.get("n_steps", 1)), 0,
            )
            prog.step()
            self.warmed = True
        elif op == "assign":
            self.shard = int(cmd["shard"])
            self.n_steps = int(cmd["n_steps"])
            self.step_wall_s = float(cmd.get("step_wall_s", 0.0))
            self.ckpt_every_steps = int(cmd.get("ckpt_every_steps", DEFAULT_CKPT_EVERY_STEPS))
            self.program = make_program(
                self.workload, self.seed, int(cmd.get("n_shards", 1)), self.n_steps, self.shard
            )
            self.step = 0
            self.done = False
            if cmd.get("resume"):
                ck = self.spool.read_checkpoint(self.shard)
                if ck is not None:
                    self.program.load_state(ck["state"])
                    self.step = int(ck["step"])
        return None

    # ---------------------------------------------------------------- loop ---
    def run(self) -> int:
        started_s = self.clock()
        self._heartbeat("idle")
        while True:
            if self.abort_after_s is not None and self.clock() - started_s > self.abort_after_s:
                return self._exit(contract.EXIT_STALLED)
            cmd = self.spool.read_command(self.wid)
            if cmd is not None and int(cmd.get("seq", -1)) > self.last_seq:
                self.last_seq = int(cmd["seq"])
                code = self._apply_command(cmd)
                if code is not None:
                    return code
            if self.program is not None and self.step < self.n_steps:
                t0 = self.clock()
                self.program.step()
                compute_s = self.clock() - t0
                self.step += 1
                if self.step % self.ckpt_every_steps == 0 or self.step == self.n_steps:
                    self._checkpoint()
                # telemetry reports the *effective* step duration (compute
                # padded to the pace) so a slowed worker reads as a
                # straggler to the daemon's EWMA detector, while compute_s
                # keeps the raw kernel time for calibration
                pace_wall_s = self.step_wall_s * self.slow_factor
                step_latency_s = max(compute_s, pace_wall_s)
                self._heartbeat(
                    "running", step_latency_s=step_latency_s, compute_s=compute_s
                )
                if compute_s < pace_wall_s:
                    self.sleep(pace_wall_s - compute_s)
            elif self.program is not None and not self.done:
                self.done = True
                self.spool.write_result(
                    self.shard,
                    {"shard": self.shard, "steps_done": self.step,
                     "payload": self.program.result()},
                )
                self._heartbeat("done")
            else:
                self._heartbeat("done" if self.done else "idle")
                self.sleep(self.idle_poll_wall_s)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="repro.orchestrator.worker",
        description="supervised worker process (launched by the daemon)",
    )
    p.add_argument("--spool", required=True, help="spool directory shared with the daemon")
    p.add_argument("--worker-id", type=int, required=True)
    p.add_argument("--workload", default="analytic")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--abort-after-s", type=float, default=None,
        help="self-watchdog: exit with the stalled code after this many wall seconds",
    )
    a = p.parse_args(argv)
    if a.workload in JAX_WORKLOADS:
        from repro.utils.backend import use_compile_cache

        use_compile_cache()
    w = Worker(
        Spool(a.spool), a.worker_id, a.workload, a.seed, abort_after_s=a.abort_after_s
    )
    return w.run()


if __name__ == "__main__":
    sys.exit(main())
