"""Multi-device integration tests — each spawns a subprocess with its own
XLA_FLAGS (device count locks at first jax init, so the main pytest process
must stay single-device)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(ROOT, "src")
_PP = os.environ.get("PYTHONPATH")
ENV = {**os.environ, "PYTHONPATH": _SRC + os.pathsep + _PP if _PP else _SRC}


def _run(args, timeout=900):
    return subprocess.run(
        [sys.executable] + args, cwd=ROOT, env=ENV,
        capture_output=True, text=True, timeout=timeout,
    )


def test_quantized_collectives_8dev():
    """Exchange.pmean / pmean_tree unbiasedness + replica agreement on 8
    devices (payload migrated off the retired compressed_collectives
    wrappers onto the Exchange seam)."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_collectives.py")])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_train_step_wire_metric_8dev():
    """metrics["wire_bytes"] emitted by the train step == the trace-time
    wire recorder, across (bits, mode), on 8 devices."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_train_metrics.py")])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_sync_every_local_updates_8dev():
    """sync_every=K: the paper's qgenx optimizer with exchanges gated to
    every K-th step — bytes only on sync steps, recorder agreement, ~K×
    wire reduction, nonzero drift between syncs."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_sync_exchange.py")])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_recenter_wire_accounting_8dev():
    """Compressed parameter re-centering + the one-call optda schedule on
    8 devices: bytes only on re-center steps, recorder agreement to the
    byte, drift strictly reduced for exactly one extra exchange."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_recenter.py")])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_coded_estimate_split_4dev():
    """The Theorem-2 estimate split over 4 devices equals the whole-tree
    pass on a replicated tree (uneven shares, gated, and through the
    train step)."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_coded_split.py")])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_fault_tolerance_8dev():
    """Acceptance: guard + fault schedule (NaN@5/worker2, drop@8-10/
    worker3) completes all steps with exactly one rejection, byte-exact
    alive-set wire accounting, bitwise pre-fault parity with a clean run,
    and the all-ones-mask bits{4,8} x mode{gather,two_phase} parity grid."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_faults.py")])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_error_feedback_8dev():
    """EF21 acceptance: qgenx(optda) + ef21-topk trains with guard on 8
    devices — trace recorder == analytic wire to the byte, per-worker
    error rows diverge, guard rejection freezes the memory bit-exactly,
    checkpoint round-trip preserves it, placeholder states fail loudly,
    and the no-EF qgenx path stays bitwise equal to the legacy
    ``compressed_pmean_tree`` across bits{4,8} x mode{gather,two_phase}."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_error_feedback.py")],
             timeout=1200)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_bucketed_8dev():
    """Bucketed overlapped exchange acceptance: per-bucket recorder ==
    analytic wire == the train step's wire_bytes metric with
    num_buckets=4, and the defer_tail pending buffer advances on
    successful syncs, bit-freezes through a guard-rejected step, and
    survives a checkpoint round-trip."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_bucketed.py")],
             timeout=1200)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_serve_wire_accounting_8dev():
    """Serving-path wire accounting: the engine's per-step logit-exchange
    bytes == the trace-time recorder on 8 devices (compressed path), the
    analytic total accumulates per packed decode step, and compressed
    logits move fewer bytes than the exact fp32 exchange."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_serve_wire.py")])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_serve_faults_8dev():
    """Hardened-serving acceptance: guarded 8-device fault drill
    (nan_logits@5:slot=2 quarantined typed after the full re-keyed retry
    budget, slot_drop victims dropped typed, every surviving request
    bit-identical to a clean guarded run, arena fully refilled) plus the
    crash/restart legs through the serve CLI (crash@6 dies with the
    dedicated exit code mid-decode; a relaunch against the same snapshot
    dir resumes every in-flight request from its last committed token
    and finishes the whole workload ok with zero page leak)."""
    r = _run([os.path.join(ROOT, "tests", "_multidev_serve_faults.py")],
             timeout=1200)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ALL OK" in r.stdout


def test_serve_cli_8dev():
    """The serve CLI on 8 forced host devices: paged int8 cache, packed
    continuous batching, logit exchange reporting wire bytes."""
    r = _run([
        "-m", "repro.launch.serve",
        "--reduced", "--host-devices", "8",
        "--batch", "2", "--requests", "3", "--prompt-len", "8",
        "--gen", "6", "--kv-bits", "8", "--logit-exchange", "int8",
    ])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "logit exchange over 8 devices" in r.stdout
    assert "mid_decode_admits" in r.stdout


def test_train_qgenx_optimizer_8dev():
    """Acceptance: --optimizer qgenx trains via the CLI on 8 devices with
    a compressed exchange and the local-update regime."""
    r = _run([
        "-m", "repro.launch.train",
        "--arch", "tinyllama-1.1b", "--reduced", "--host-devices", "8",
        "--steps", "16", "--batch", "16", "--seq", "32",
        "--repeat-batch",
        "--optimizer", "qgenx", "--gamma-scale", "0.02",
        "--compression", "int8", "--compress-axis", "data",
        "--sync-every", "4", "--log-every", "4",
    ])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("[train] step=")]
    first = float(lines[0].split("loss=")[1].split()[0])
    last = float(lines[-1].split("loss=")[1].split()[0])
    assert last < first, (first, last)


def test_train_compressed_8dev():
    """End-to-end: 8-way DP training with int8 two-phase exchange learns."""
    r = _run([
        "-m", "repro.launch.train",
        "--arch", "tinyllama-1.1b", "--reduced", "--host-devices", "8",
        "--steps", "25", "--batch", "16", "--seq", "64",
        "--lr", "3e-3", "--repeat-batch",
        "--compression", "int8", "--compress-axis", "data",
        "--optimizer", "extra_adam", "--log-every", "5",
    ])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("[train] step=")]
    first = float(lines[0].split("loss=")[1].split()[0])
    last = float(lines[-1].split("loss=")[1].split()[0])
    assert last < first, (first, last)


def test_train_leafwise_exchange_8dev():
    """The production-mesh exchange path (sharding-preserving leafwise
    int8) trains end-to-end."""
    r = _run([
        "-m", "repro.launch.train",
        "--arch", "tinyllama-1.1b", "--reduced", "--host-devices", "8",
        "--steps", "20", "--batch", "16", "--seq", "64",
        "--lr", "3e-3", "--repeat-batch",
        "--compression", "int8", "--compress-axis", "data",
        "--compress-mode", "leafwise",
        "--optimizer", "extra_adam", "--log-every", "5",
    ])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("[train] step=")]
    first = float(lines[0].split("loss=")[1].split()[0])
    last = float(lines[-1].split("loss=")[1].split()[0])
    assert last < first, (first, last)


def test_train_fp32_vs_int8_similar_loss():
    """Unbiased compression: loss curve close to FP32 at equal steps."""
    outs = {}
    for comp in ("none", "int8"):
        r = _run([
            "-m", "repro.launch.train",
            "--arch", "gemma-2b", "--reduced", "--host-devices", "4",
            "--steps", "20", "--batch", "8", "--seq", "64",
            "--lr", "3e-3", "--repeat-batch",
            "--compression", comp, "--compress-axis", "data",
            "--optimizer", "adam",
        ])
        assert r.returncode == 0, r.stderr[-2000:]
        outs[comp] = float(r.stdout.split("final_loss=")[1].split()[0])
    assert abs(outs["int8"] - outs["none"]) < 0.8, outs
