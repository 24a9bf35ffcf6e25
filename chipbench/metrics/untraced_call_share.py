"""Share of the traced calls' time that no span explains: 1 - the part of
the harness's request spans under a program span (``repro.*``), a jitted
call (``PjitFunction(*)``) or a fetch (``np.asarray(jax.Array)``), over the
request spans' length."""
from chipbench import spans


def read(run):
    return spans.untraced_share(run)
