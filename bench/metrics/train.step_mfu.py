"""The whole step's share of the chips' peak: the operations of the two
forward and backward passes an extragradient step requires (from the
configuration's shapes, recomputation not counted) times steps per
second of the traced window, over chips x peak bf16 FLOP/s."""

MOVES = "train_tokens_per_s"


def read(r):
    if r.get("kind") != "train":
        return None
    steps_per_s = r["steps"] / (r["reduced"].window_ns / 1e9)
    peak = r["chips"] * r["peaks"]["bf16_flops_per_s"]
    return 100.0 * r["flops_per_step"] * steps_per_s / peak
