"""Device ms per step, on the slowest chip, of the rest of
``train.compute_ms``: non-collective ops outside the exchange that lie in
no stage scope (compiler-inserted copies, the key split).  With the four
stages before it, it sums to ``train.compute_ms``."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.train_ms(r, "unscoped")
