"""The benchmark's operation and byte counts against hand counts."""

import json
import os

import pytest

from bench import flops
from bench.reference import qwen3

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_qwen3_4b_parameter_count_by_hand():
    c = dict(_config("qwen3-4b-l1"), num_hidden_layers=36)  # as published
    attn = 2560 * (32 + 8 + 8) * 128 + 32 * 128 * 2560  # q, k, v; o
    mlp = 3 * 2560 * 9728  # gate, up, down
    matmul = 36 * (attn + mlp) + 151936 * 2560  # + tied logits
    assert qwen3.matmul_params(c) == matmul == 4_022_272_000
    norms = 36 * (2 * 2560 + 2 * 128) + 2560
    assert qwen3.param_count(c) == matmul + norms


def test_one_layer_cut_step_flops_by_hand():
    c = _config("qwen3-4b-l1")
    n = 2560 * 48 * 128 + 4096 * 2560 + 3 * 2560 * 9728 + 151936 * 2560
    tokens, seq = 16 * 128, 128
    fwd = tokens * (2 * n + 4 * 1 * 32 * 128 * seq)
    assert flops.extragradient_step_flops(c, 16, seq) == pytest.approx(
        2 * 3 * fwd)


def test_two_phase_exchange_bytes_by_hand():
    n, k, bucket = 4096, 4, 512
    norms = 4 * n / bucket  # 32 bytes per full pass of norms
    int8 = (4 * n                      # float32 gradient read
            + (n + norms)              # quantized payload written
            + (n + norms)              # every chunk read for the reduce
            + (n + norms) / k          # reduced chunk written
            + (n + norms)              # every chunk read back
            + 4 * n)                   # float32 mean written
    assert flops.exchange_bytes(n, k, 8, bucket) == pytest.approx(int8)
    int4 = 8 * n + 3 * (n / 2 + norms) + (n / 2 + norms) / k
    assert flops.exchange_bytes(n, k, 4, bucket) == pytest.approx(int4)
