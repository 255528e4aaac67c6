"""Device ms per step, on the slowest chip, of the train step's backward
passes: non-collective ops under the ``backward`` scope, or carrying
jax's ``transpose(`` marker in an oracle round's scope, outside the
exchange and the optimizer."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.train_ms(r, "backward")
