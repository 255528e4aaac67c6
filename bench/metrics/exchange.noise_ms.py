"""Device ms per step, on the slowest chip, of the exchange's rounding
noise: non-collective ops under ``exchange/`` and ``noise`` (the uniform
draws of stochastic rounding, or the device PRNG's seeds)."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.exchange_ms(r, "noise")
