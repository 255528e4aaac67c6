"""Device ms per step, on the slowest chip, of the exchange's final
dequantize (another compressor's decode): non-collective ops under
``exchange/`` and ``dequantize``."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.exchange_ms(r, "dequantize")
