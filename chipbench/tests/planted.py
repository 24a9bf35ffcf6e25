"""Entry points with a fault planted where the oracle's answer is produced.

A run's requests are answered in fresh planning children, so a fault has to
be planted there: a traffic mix whose ``entry`` names one of these runs it
in the child, then calls ``choose_strategy`` as the sound mix does."""
import dataclasses

import numpy as np

from repro.orchestrator.plan import choose_strategy


def _wrap_replay(edit):
    from repro.scenarios import trajectory

    orig = trajectory.replay_batch

    def broken(*args, **kwargs):
        out = orig(*args, **kwargs)
        edit(out)
        return out

    trajectory.replay_batch = broken


def state_unchanged(spec, **kw):
    """The fold never advances: every slot of every tape is a no-op."""
    from repro.scenarios import trajectory

    orig = trajectory.compile_batch

    def frozen(*args, **kwargs):
        batch = orig(*args, **kwargs)
        return dataclasses.replace(batch, valid=np.zeros_like(batch.valid))

    trajectory.compile_batch = frozen
    return choose_strategy(spec, **kw)


def half_batch(spec, **kw):
    """Half of each batch's campaigns left out; the scores taken over the rest."""
    from repro.scenarios import montecarlo

    orig = montecarlo.mc_trajectories

    def half(spec, strategy, n_seeds=1000, seed=0, batch=None, **kwargs):
        h = batch.n_seeds // 2
        kept = dataclasses.replace(batch, **{
            f.name: getattr(batch, f.name)[:h]
            for f in dataclasses.fields(batch) if isinstance(getattr(batch, f.name), np.ndarray)
        })
        return orig(spec, strategy, n_seeds=h, seed=seed, batch=kept, **kwargs)

    montecarlo.mc_trajectories = half
    return choose_strategy(spec, **kw)


def answer_altered(spec, **kw):
    """One surviving trial's makespan one second off where it is produced."""
    def edit(out):
        i = int(np.flatnonzero(out["survived"])[0])
        out["total_s"] = out["total_s"].copy()
        out["total_s"][i] += 1.0

    _wrap_replay(edit)
    return choose_strategy(spec, **kw)
