"""Device ms per step, on the slowest chip, of the step's metrics and its
guard: non-collective ops under ``step_metrics`` (loss mean, wire
accounting, the Theorem-2 code length, the drift probe) or ``guard``."""

from bench import scopes

MOVES = "train_tokens_per_s"


def read(r):
    return scopes.train_ms(r, "step_metrics")
