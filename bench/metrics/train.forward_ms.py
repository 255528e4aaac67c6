"""Device ms per step, on the slowest chip, of the train step's forward
passes: non-collective ops under the ``forward`` scope, or in an oracle
round's scope (``lookahead``, ``commit``) without jax's ``transpose(``
marker, outside the exchange and the optimizer."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.train_ms(r, "forward")
