"""Operations and bytes the algorithms need, from a configuration's shapes.

These count the work the model and the exchange require, whatever code
carries it out, so a later change to the program cannot make them stale.
Conventions:

* A matmul of [m, k] by [k, n] is 2mkn operations.  Each architecture's
  module (``bench/reference/<arch>.py``: ``forward_flops``,
  ``param_count``) counts its forward pass so; a decoder's attention is
  ``4 x layers x heads x head_dim x S`` per token (scores and values over
  the whole context, as in the PaLM accounting).  Backward is twice the
  forward; recomputation is not counted.
* The exchange of ``n`` float32 coordinates among ``k`` workers under
  ``two_phase`` quantization needs, on each worker: the gradient read
  once (4n), its quantized payload written (q n) and, after the
  all-to-all, every worker's chunk read for the reduce (q n), the reduced
  chunk's payload written (q n / k), after the all-gather every chunk
  read back (q n), and the float32 mean written once (4n); plus a float32
  norm per bucket wherever a payload is written or read.  ``q`` is bytes
  per coordinate (1 for int8, 1/2 for int4).
"""

from __future__ import annotations

from bench import reference


def extragradient_step_flops(c: dict, batch: int, seq: int) -> float:
    """One extragradient step: two forward and backward passes over the
    global batch."""
    return 2 * 3 * reference.of(c).forward_flops(c, batch * seq, seq)


def exchange_bytes(n: int, workers: int, bits: int, bucket: int) -> float:
    """HBM bytes one worker's ``two_phase`` exchange of ``n`` coordinates
    needs (module docstring)."""
    q = bits / 8.0
    norms = 4.0 * n / bucket
    written = q * n + norms + (q * n + norms) / workers
    read = 2 * (q * n + norms)
    return 4.0 * n + written + read + 4.0 * n
