"""Device ms per step, on the slowest chip, of collective operations on
the chip's op stream: time in which the chip ran a collective and no
compute beside it (asynchronous transfers hidden behind compute do not
appear on that stream)."""

from bench import readings

MOVES = "train_tokens_per_s"


def read(r):
    if r.get("kind") != "train" or r.get("exchange_bytes_per_step") is None:
        return None
    return readings.step_split_ms(r)[0]
