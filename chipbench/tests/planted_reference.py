"""A reference with a fault planted: it names the default reference's
slowest candidate the winner. A configuration that names it has to come
out not correct against the sound program."""
from chipbench import reference


def decide(spec, candidates, n_seeds, base_seed, reinstate_s, **kw):
    _, scores = reference.decide(spec, candidates, n_seeds, base_seed, reinstate_s, **kw)
    return max(candidates, key=lambda n: scores[n]["mean_s"]), scores
