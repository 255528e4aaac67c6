"""The comparison that decides ``correct``, driven through a whole run of
each cell at a size a test can hold (the cells' widths cut, on the CPU),
with the harness's look for a chip skipped.

Each fault of the timed path is planted in the program and must turn
``correct`` false: a step that returns its state unchanged, half of each
chip's batch left out, the exchange between chips left out (on four
virtual CPU devices in a child process).  The control, the program with
its own next lower exchange precision (int4), must fail too.  Limits here
are for this size; the cells' own limits are set from chip readings
(PERF.md, section 2).
"""

import json
import os
import subprocess
import sys
import time
from unittest import mock

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: limits at this size, between the sound program's readings (loss 1.2e-3,
#: gradient 1.6e-2, change 0.12) and the control's and faults' (gradient
#: >= 0.08, change 1.0 for a state left unchanged)
TRAIN_LIMITS = {"loss_gap": 0.005, "grad_norm_gap": 0.04,
                "update_norm_gap": 0.5}
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=128, vocab_size=512)
TINY_PROGRAM = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                    d_ff=128, vocab_size=512)


def tiny_run(cell, config: str, traffic_name: str, **traffic):
    from bench import harness
    from bench.traffic import generate

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(HERE, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    layers = cfg["num_hidden_layers"]
    cfg.update(TINY, num_hidden_layers=min(layers, 2))
    cfg["program"].update(TINY_PROGRAM, num_layers=min(layers, 2))
    mix = dict(generate.load(traffic_name), **traffic)
    run = harness.Run(spec, cell, cfg, mix, {},
                      seed=2 ** 33 + 17, seconds=1.0, trace=False,
                      t_start=time.perf_counter())
    harness.claim_chips(run, allow_cpu=True)
    return run


# -- in a child process with four CPU devices -------------------------------


def _train_case(case: str) -> dict:
    from bench import train_cell

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]
    run = tiny_run(cell, "qwen3-4b-l1", "train.dp4-int8", seq_len=32)
    run.limits = {"numbers": TRAIN_LIMITS}
    patches = []
    if case == "control_int4":
        run.traffic["argv"] = [("int4" if a == "int8" else a)
                               for a in run.traffic["argv"]]
    elif case == "unchanged":
        patches.append(mock.patch(
            "repro.optim.optimizers.commit",
            lambda cfg, params, state, grads: (params, state)))
    elif case == "half_batch":
        from repro.launch import steps

        base = steps.make_loss_fn

        def half(model):
            f = base(model)
            return lambda p, b: f(p, {k: v[: v.shape[0] // 2]
                                      for k, v in b.items()})

        patches.append(mock.patch("repro.launch.steps.make_loss_fn", half))
    elif case == "no_exchange":
        patches.append(mock.patch(
            "repro.core.exchange.Compressor.pmean_tree_bucketed",
            lambda self, tree, cfg, state, key, axis_index=None:
            (tree, state.pending)))
    for p in patches:
        p.start()
    try:
        out = train_cell.run_cell(run)
    finally:
        for p in patches:
            p.stop()
    return {"correct": out["correct"], "checks": out["checks"]}


TRAIN_CASES = ("sound", "control_int4", "unchanged", "half_batch",
               "no_exchange")


@pytest.fixture(scope="module")
def train_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, __file__, *TRAIN_CASES], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_training_run_is_correct(train_results):
    assert train_results["sound"]["correct"], train_results["sound"]


@pytest.mark.parametrize("case", TRAIN_CASES[1:])
def test_control_and_faults_make_training_incorrect(train_results, case):
    assert not train_results[case]["correct"], train_results[case]


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    results = {c: _train_case(c) for c in sys.argv[1:]}
    print(json.dumps(results))
