"""Device ms per step, on the slowest chip, of the train step's
operations outside the exchange's scopes and outside the collectives:
forward, backward and optimizer."""

from bench import readings

MOVES = "train_tokens_per_s"


def read(r):
    if r.get("kind") != "train":
        return None
    return readings.step_split_ms(r)[2]
