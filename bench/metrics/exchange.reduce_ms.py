"""Device ms per step, on the slowest chip, of the exchange's
dequant-reduce(-requantize), the fused Pallas kernel or its jnp twin:
non-collective ops under ``exchange/`` and ``reduce``."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.exchange_ms(r, "reduce")
