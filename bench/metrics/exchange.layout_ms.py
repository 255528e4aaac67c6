"""Device ms per step, on the slowest chip, of the exchange's layout: its
non-collective ops in none of the four kernel scopes (pad, pack, reshape,
unpack, the state update).  With the four kernels it sums to
``exchange.device_ms``."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.exchange_ms(r, "layout")
