"""The exchange's roofline share: the HBM bytes its work requires per step
(``bench/flops.py``: the float32 gradient read once, the quantized
payload written and read as ``two_phase`` requires, the mean written
once, for both exchanges of an extragradient step) at peak HBM bandwidth,
over ``exchange.device_ms``.  Memory bounds it: its operations per byte
are far below the chip's ridge point."""

from bench import readings

MOVES = "train_tokens_per_s"


def read(r):
    if r.get("kind") != "train" or r.get("exchange_bytes_per_step") is None:
        return None
    ms = readings.step_split_ms(r)[1]
    if ms <= 0:
        return None
    least_ms = 1e3 * r["exchange_bytes_per_step"] / r["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
