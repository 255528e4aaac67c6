"""Share of the traced train window in which the slowest chip ran no
operation: 1 - (union of its op intervals) / window."""

from bench import readings

MOVES = "train_tokens_per_s"


def read(r):
    if r.get("kind") != "train":
        return None
    return readings.idle_share(r)
