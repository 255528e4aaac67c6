"""Device ms per step, on the slowest chip, of the exchange's phase-1
quantize (the Pallas kernel or its jnp reference; another compressor's
encode): non-collective ops under ``exchange/`` and ``quantize``."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.exchange_ms(r, "quantize")
