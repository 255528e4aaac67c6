"""The train loop's host spans and its recompile counter: with
``--profile-dir`` the trace holds one ``train`` step span per step with the
loop's phases inside, read back with ``jax.profiler.ProfileData``; a normal
run counts no recompile of the step."""

import collections
import glob

import jax
import numpy as np
import pytest

PHASES = ("train", "data", "dispatch", "wait", "metric fetch",
          "checkpoint save", "watchdog snapshot")


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    import contextlib
    import io

    from repro.launch import train

    d = tmp_path_factory.mktemp("train_spans")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        train.main([
            "--arch", "tinyllama-1.1b", "--reduced", "--steps", "3",
            "--batch", "2", "--seq", "16", "--compression", "int8",
            "--guard", "--log-every", "10",
            "--checkpoint-dir", str(d / "ckpt"), "--checkpoint-every", "2",
            "--profile-dir", str(d / "profile"),
        ])
    (path,) = glob.glob(str(d / "profile" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    from jax.profiler import ProfileData

    spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PHASES:
                        spans[ev.name].append({k: v for k, v in ev.stats})
    return out.getvalue(), spans


def test_one_step_span_per_step(traced_run):
    _, spans = traced_run
    assert sorted(s["step_num"] for s in spans["train"]) == [0, 1, 2]


def test_loop_phases_are_spans(traced_run):
    _, spans = traced_run
    for phase in ("data", "dispatch", "wait", "metric fetch",
                  "watchdog snapshot"):
        assert len(spans[phase]) >= 3, phase
    assert len(spans["checkpoint save"]) == 1


def test_a_normal_run_counts_no_recompile(traced_run):
    out, _ = traced_run
    last = out.strip().splitlines()[-1]
    assert last.startswith("[train] done.") and last.endswith(
        "recompiles=0"), last
    assert "RECOMPILED" not in out


def test_compile_counter_counts_backend_compiles_while_entered():
    from repro.launch.train import CompileCounter

    f = jax.jit(lambda x: x * 2 + 1)
    with CompileCounter() as c:
        f(np.ones(7, np.float32))
        assert c.count == 1
        f(np.ones(7, np.float32))
        assert c.count == 1  # cached: no compile
        f(np.ones(9, np.float32))  # a new shape compiles again
        assert c.count == 2
    f(np.ones(11, np.float32))
    assert c.count == 2  # not listening once exited
