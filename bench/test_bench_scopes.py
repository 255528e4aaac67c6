"""The ten stage readers (``bench/scopes.py``) on a traced run of the
program's real step: the cell's argv at a small width on four CPU devices
(a child process), through ``train_cell.run_cell`` with ``--trace 1``.
Each reads a finite value of at least 0, and the two groups split
``train.compute_ms`` and ``exchange.device_ms`` exactly."""

import json
import math
import os
import subprocess
import sys

import pytest

from bench import scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRAIN = ["train.forward_ms", "train.backward_ms", "train.optimizer_ms",
         "train.step_metrics_ms", "train.unscoped_ms"]
EXCHANGE = ["exchange.noise_ms", "exchange.quantize_ms", "exchange.reduce_ms",
            "exchange.dequantize_ms", "exchange.layout_ms"]


def _traced_metrics() -> dict:
    from bench import train_cell
    from bench.test_bench_correct import tiny_run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]
    run = tiny_run(cell, "qwen3-4b-l1", "train.dp4-int8", seq_len=32)
    run.trace = True
    run.limits = {"numbers": {}}
    out = train_cell.run_cell(run)
    return {k: v["value"] for k, v in out["metrics"].items()}


@pytest.fixture(scope="module")
def metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", TRAIN + EXCHANGE)
def test_each_stage_reads_a_finite_time(metrics, name):
    assert name in metrics, sorted(metrics)
    assert math.isfinite(metrics[name]) and metrics[name] >= 0


@pytest.mark.parametrize("group,whole", [(TRAIN, "train.compute_ms"),
                                         (EXCHANGE, "exchange.device_ms")])
def test_stages_split_their_metric_exactly(metrics, group, whole):
    total = sum(metrics[n] for n in group)
    assert total == pytest.approx(metrics[whole], rel=1e-6)
    assert metrics[whole] > 0


def test_the_step_names_its_stages(metrics):
    """At this size the forward, backward, optimizer and every exchange
    kernel take time."""
    for name in ("train.forward_ms", "train.backward_ms",
                 "train.optimizer_ms", "exchange.noise_ms",
                 "exchange.quantize_ms", "exchange.reduce_ms",
                 "exchange.dequantize_ms"):
        assert metrics[name] > 0, name


@pytest.mark.parametrize("op_name,stage", [
    ("jit(s)/shard_map/lookahead/forward/jvp()/dot_general", "forward"),
    ("jit(s)/shard_map/commit/backward/transpose(jvp())/mul", "backward"),
    ("jit(s)/shard_map/commit/jvp()/dot_general", "forward"),
    ("jit(s)/shard_map/commit/transpose(jvp())/dot_general", "backward"),
    ("jit(s)/shard_map/commit/update/sub", "optimizer"),
    ("jit(s)/shard_map/step_metrics/reduce_sum", "step_metrics"),
    ("jit(s)/shard_map/guard/cond/select_n", "step_metrics"),
    ("jit(s)/shard_map/split", "unscoped"),
    ("", "unscoped"),
    ("jit(s)/shard_map/commit/exchange/bucket0/noise/jit(_uniform)/xor",
     "noise"),
    ("jit(s)/shard_map/lookahead/exchange/bucket1/quantize/jit(q)/pallas_call",
     "quantize"),
    ("jit(s)/shard_map/commit/exchange/reduce/reduce", "reduce"),
    ("jit(s)/shard_map/commit/exchange/dequantize/mul", "dequantize"),
    ("jit(s)/shard_map/commit/exchange/bucket0/pack/reduce", "layout"),
    ("jit(s)/shard_map/commit/exchange/pad", "layout"),
])
def test_stage_of_an_op_name(op_name, stage):
    assert scopes.stage(op_name) == stage


def test_a_program_without_stage_names_reads_nothing():
    r = {"kind": "train", "exchange_bytes_per_step": 1.0, "steps": 1,
         "op_names": {"fusion.1": "jit(s)/shard_map/exchange/bucket0/pack/x"}}
    assert scopes.train_ms(r, "forward") is None
    assert scopes.exchange_ms(r, "layout") is None


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    print(json.dumps(_traced_metrics()))
