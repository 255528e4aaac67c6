"""Device ms per step, on the slowest chip, of the optimizer: non-collective
ops under the ``update`` scope (clipping, moments, extrapolation and the
committed update; parameter re-centering) outside the exchange."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.train_ms(r, "optimizer")
