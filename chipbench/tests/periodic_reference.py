"""A reference composed from :mod:`chipbench.reference`'s pieces, as a
configuration whose failure process the default lacks brings it: the
paper's periodic pattern (Table 1), one failure ``offset_s`` after each
window's start on a uniform node, added to the default kinds. The
configuration's file would name it ``"reference": "tests.periodic_reference"``."""
import functools
import math

import numpy as np

from chipbench import reference


def periodic(spec, p, rng, seed, idx):
    """``per_window`` failures a window, the ``k``-th at ``offset_s + 0.9 k
    period / per_window`` past its start, from a generator of the process's
    own seed, as the paper's pattern draws them."""
    rng = np.random.default_rng(p.get("seed", seed + 1_000_003 * idx))
    horizon = spec["horizon_s"]
    period = p.get("period_s", spec["period_s"])
    per_window = p.get("per_window", 1)
    out = []
    for w in range(int(math.ceil(horizon / period))):
        for k in range(per_window):
            t = w * period + p.get("offset_s", 900.0) + k * (period / max(per_window, 1)) * 0.9
            if t >= horizon:
                continue
            node = int(rng.integers(0, spec["n_nodes"]))
            rng.random()  # whether the failure is predictable: no detector here reads it
            out.append((float(t), node))
    return out


stream = functools.partial(reference.failure_stream, kinds={**reference.KINDS, "periodic": periodic})


def decide(spec, candidates, n_seeds, base_seed, reinstate_s, **kw):
    return reference.decide(spec, candidates, n_seeds, base_seed, reinstate_s, stream=stream, **kw)
