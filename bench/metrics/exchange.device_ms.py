"""Device ms per step, on the slowest chip, of the non-collective
operations under the exchange's ``exchange/`` scopes (pack, quantize,
dequant-reduce, unpack; the Pallas kernels included)."""

from bench import readings

MOVES = "train_tokens_per_s"


def read(r):
    if r.get("kind") != "train" or r.get("exchange_bytes_per_step") is None:
        return None
    ms = readings.step_split_ms(r)[1]
    return ms if ms > 0 else None
