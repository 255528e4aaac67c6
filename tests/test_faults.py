"""Fault-tolerance layer: spec grammar, step guard, watchdog policy,
crash-safe checkpoints, and the interrupted-save -> resume-to-same-loss
end-to-end path (DESIGN.md §8).

Single-device (tier-1) coverage; the 8-device acceptance run (dropout +
NaN-poison + byte-exact alive-set wire accounting + the all-ones-mask
parity grid) lives in tests/_multidev_faults.py via test_multidevice.py.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.checkpoint import checkpointing
from repro.core import faults
from repro.core.exchange import (
    ExchangeConfig,
    make_exchange,
    null_exchange_state,
)
from repro.core.faults import FaultSpec, Watchdog
from repro.core.quantization import QuantConfig


# ---------------------------------------------------------------------------
# FaultSpec grammar
# ---------------------------------------------------------------------------


def test_parse_full_grammar():
    spec = FaultSpec.parse(
        "nan_grad@5:worker=2; drop@8-10:worker=3 ;wire_corrupt@6;"
        "ckpt_truncate@12"
    )
    assert len(spec.events) == 4
    e = spec.of_kind("drop")[0]
    assert (e.start, e.end, e.worker) == (8, 10, 3)
    assert spec.of_kind("nan_grad")[0].worker == 2
    assert spec.of_kind("wire_corrupt")[0].worker is None
    assert spec.has_device_events
    assert spec.ckpt_faults_at(12) == ("ckpt_truncate",)
    assert spec.ckpt_faults_at(11) == ()


def test_parse_empty_and_none():
    assert FaultSpec.parse("").events == ()
    assert FaultSpec.parse(None).events == ()
    assert not FaultSpec.parse("ckpt_truncate@3").has_device_events


@pytest.mark.parametrize("bad", [
    "nan_grad",               # no @STEP
    "meteor_strike@5",        # unknown kind
    "nan_grad@x",             # bad step
    "drop@9-5",               # empty range
    "nan_grad@5:replica=2",   # unknown option
])
def test_parse_rejects_malformed(bad):
    with pytest.raises(ValueError):
        FaultSpec.parse(bad)


def test_traced_predicates():
    spec = FaultSpec.parse("drop@3-4:worker=1;nan_grad@2")
    # liveness: worker 1 dead exactly on steps 3-4
    live = jax.jit(lambda s, w: spec.liveness(s, w))
    assert float(live(jnp.int32(3), jnp.int32(1))) == 0.0
    assert float(live(jnp.int32(3), jnp.int32(0))) == 1.0
    assert float(live(jnp.int32(5), jnp.int32(1))) == 1.0
    # no drop events -> Python None (jaxpr untouched)
    assert FaultSpec.parse("nan_grad@2").liveness(jnp.int32(2), 0) is None
    # poison: NaN on the scheduled step, bitwise identity off it
    g = {"w": jnp.ones((4,), jnp.float32)}
    on = spec.poison_grads(g, jnp.int32(2), jnp.int32(0))
    off = spec.poison_grads(g, jnp.int32(1), jnp.int32(0))
    assert not np.isfinite(np.asarray(on["w"])).any()
    np.testing.assert_array_equal(np.asarray(off["w"]), np.asarray(g["w"]))


def test_tree_all_finite():
    ok = {"a": jnp.ones((3,)), "n": jnp.arange(3)}  # int leaf skipped
    assert bool(faults.tree_all_finite(ok))
    assert not bool(faults.tree_all_finite(ok, {"b": jnp.float32(np.nan)}))
    assert not bool(faults.tree_all_finite({"b": jnp.float32(np.inf)}))
    assert bool(faults.tree_all_finite({"i": jnp.int32(7)}))  # no float leaf


# ---------------------------------------------------------------------------
# Watchdog policy
# ---------------------------------------------------------------------------


def test_watchdog_consecutive_trigger():
    wd = Watchdog(rollback_after=3)
    wd.record_good(0, {"x": jnp.ones((2,))})
    assert not wd.observe(1, rejected=True, nonfinite=True)
    assert not wd.observe(2, rejected=True, nonfinite=True)
    assert wd.observe(3, rejected=True, nonfinite=True)
    step, trees = wd.rollback()
    assert step == 0 and wd.consecutive == 0 and wd.rollbacks == 1
    np.testing.assert_array_equal(np.asarray(trees["x"]), np.ones((2,)))


def test_watchdog_rate_trigger():
    # 1-in-a-row never reaches rollback_after=3, but 50% of the window does
    wd = Watchdog(rollback_after=3, divergence_rate=0.5, window=6)
    wd.record_good(0, {"x": jnp.zeros(())})
    fired = []
    for t in range(12):
        fired.append(wd.observe(t, rejected=(t % 2 == 0), nonfinite=False))
    assert any(fired)


def test_watchdog_without_snapshot_never_fires():
    wd = Watchdog(rollback_after=1)
    assert not wd.observe(0, rejected=True, nonfinite=True)
    assert wd.rejected_steps == 1 and wd.nonfinite_steps == 1


def test_watchdog_validates_args():
    with pytest.raises(ValueError):
        Watchdog(rollback_after=0)
    with pytest.raises(ValueError):
        Watchdog(divergence_rate=1.5)


# ---------------------------------------------------------------------------
# Step guard (single device; 8-dev version in _multidev_faults.py)
# ---------------------------------------------------------------------------


def _tiny_model():
    from repro.configs.registry import get_config
    from repro.models.model import build

    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    return build(cfg)


def test_guard_rejects_and_carries_state():
    """NaN-poisoned step: rejected=1 and params/opt_state bitwise
    unchanged; clean steps bitwise match the unguarded step."""
    from repro.launch.steps import make_train_step
    from repro.optim import optimizers as opt

    model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    ocfg = opt.OptimizerConfig(name="adam", lr=1e-3)
    ost = opt.init_state(ocfg, params)
    exst = null_exchange_state()
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    key = jax.random.PRNGKey(1)

    base = jax.jit(make_train_step(model, ocfg))
    p0, o0, _, m0 = base(params, ost, exst, batch, key)

    spec = FaultSpec.parse("nan_grad@1")
    guarded = jax.jit(make_train_step(model, ocfg, guard=True,
                                      fault_spec=spec))

    def eq(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    # step 0: fault inactive -> accepted, values match the unguarded step
    p1, o1, _, m1 = guarded(params, ost, exst, batch, key, 0)
    assert float(m1["rejected"]) == 0.0 and float(m1["nonfinite"]) == 0.0
    assert eq(p0, p1) and eq(o0, o1)
    # step 1: poisoned -> rejected, carried state is the INPUT state
    p2, o2, _, m2 = guarded(params, ost, exst, batch, key, 1)
    assert float(m2["rejected"]) == 1.0 and float(m2["nonfinite"]) == 1.0
    assert eq(params, p2) and eq(ost, o2)


def test_all_ones_mask_bit_exact_1dev():
    """mask=1.0 through a compressed pmean_tree is bitwise identical to
    mask=None (K=1 slice of the 8-dev parity grid)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (300,),
                                   jnp.float32)}
    for bits, mode in ((8, "gather"), (8, "two_phase"), (4, "gather"),
                      (4, "two_phase")):
        q = QuantConfig(num_levels=15 if bits == 8 else 5, bits=bits,
                        bucket_size=256)
        ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=q,
                                          mode=mode, axis_name="data"))

        def run(with_mask):
            def f(tl, kk):
                mask = jnp.float32(1.0) if with_mask else None
                mean, _ = ex.pmean_tree(tl, ex.init_state(), kk, mask=mask)
                return mean

            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=({"w": P()}, P()),
                out_specs={"w": P()}, check_vma=False,
            ))(tree, jax.random.PRNGKey(9))

        np.testing.assert_array_equal(
            np.asarray(run(False)["w"]), np.asarray(run(True)["w"]),
            err_msg=f"bits={bits} mode={mode}")


# ---------------------------------------------------------------------------
# Crash-safe checkpoints
# ---------------------------------------------------------------------------


def _trees(v=1.0):
    return {"params": {"w": jnp.full((4, 3), v, jnp.float32)},
            "opt_state": {"m": jnp.full((4, 3), v / 2, jnp.float32)}}


def test_latest_step_missing_empty_garbage(tmp_path):
    d = str(tmp_path)
    assert checkpointing.latest_step(d) is None
    os.makedirs(d, exist_ok=True)
    open(os.path.join(d, "latest"), "w").close()  # empty
    assert checkpointing.latest_step(d) is None
    with open(os.path.join(d, "latest"), "w") as f:
        f.write("not-a-step")
    assert checkpointing.latest_step(d) is None


def test_restore_refuses_dtype_cast(tmp_path):
    d = str(tmp_path)
    checkpointing.save(d, 1, _trees())
    bad = {"params": {"w": jnp.zeros((4, 3), jnp.bfloat16)}}
    with pytest.raises(checkpointing.CheckpointStructureError) as ei:
        checkpointing.restore(d, bad)
    assert ei.value.tree == "params" and "dtype" in ei.value.detail


def test_restore_names_mismatched_tree(tmp_path):
    d = str(tmp_path)
    checkpointing.save(d, 1, _trees())
    with pytest.raises(checkpointing.CheckpointStructureError) as ei:
        checkpointing.restore(d, {"params": {"other_key": jnp.zeros((2,))}})
    assert ei.value.tree == "params"


def test_crc_catches_bit_rot(tmp_path):
    d = str(tmp_path)
    checkpointing.save(d, 1, _trees())
    npz = os.path.join(d, "ckpt_1.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # flip bits mid-payload
    with open(npz, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(checkpointing.CheckpointCorruptError):
        checkpointing.restore(d, _trees(), step=1)


def test_truncated_npz_falls_back_to_previous_step(tmp_path):
    d = str(tmp_path)
    checkpointing.save(d, 1, _trees(1.0))
    checkpointing.save(d, 2, _trees(2.0))
    faults.inject_ckpt_fault(d, 2, "ckpt_truncate")
    step, trees, reset = checkpointing.restore_with_fallback(d, _trees())
    assert step == 1 and reset == ()
    np.testing.assert_array_equal(np.asarray(trees["params"]["w"]),
                                  np.ones((4, 3), np.float32))


def test_dropped_meta_falls_back(tmp_path):
    d = str(tmp_path)
    checkpointing.save(d, 1, _trees(1.0))
    checkpointing.save(d, 2, _trees(2.0))
    faults.inject_ckpt_fault(d, 2, "ckpt_drop_meta")
    # the latest pointer still says 2; its meta is gone -> corrupt -> walk
    step, trees, _ = checkpointing.restore_with_fallback(d, _trees())
    assert step == 1


def test_garbage_latest_still_restores(tmp_path):
    d = str(tmp_path)
    checkpointing.save(d, 3, _trees(3.0))
    faults.inject_ckpt_fault(d, 3, "ckpt_garbage_latest")
    assert checkpointing.latest_step(d) is None
    step, trees, _ = checkpointing.restore_with_fallback(d, _trees())
    assert step == 3
    np.testing.assert_array_equal(np.asarray(trees["params"]["w"]),
                                  np.full((4, 3), 3.0, np.float32))


def test_structure_mismatch_does_not_walk_back(tmp_path):
    """Older checkpoints share the run config: a structure mismatch must
    raise (config change), not silently restore an ancient step."""
    d = str(tmp_path)
    checkpointing.save(d, 1, _trees(1.0))
    checkpointing.save(d, 2, _trees(2.0))
    bad = {"params": _trees()["params"],
           "opt_state": {"m": jnp.zeros((9, 9), jnp.float32)}}
    with pytest.raises(checkpointing.CheckpointStructureError):
        checkpointing.restore_with_fallback(d, bad)
    # ...unless the tree is explicitly allowed to reset
    step, trees, reset = checkpointing.restore_with_fallback(
        d, bad, allow_reset=("opt_state",))
    assert step == 2 and reset == ("opt_state",) and "opt_state" not in trees
    np.testing.assert_array_equal(np.asarray(trees["params"]["w"]),
                                  np.full((4, 3), 2.0, np.float32))


def test_legacy_checkpoint_without_error_slot_resets_named_aux(tmp_path):
    """Checkpoints written before the EF error slot (4-child ex_state:
    levels, levels_lo, hist, step) or before the PR 9 defer_tail pending
    slot (5-child: + error) must fail LOUDLY when restored into today's
    6-child ExchangeState — and under ``allow_reset=("ex_state",)`` (the
    ``--allow-ckpt-reset`` path) restore everything else while reporting
    exactly that one named auxiliary tree as reset."""
    ex = make_exchange(ExchangeConfig(
        compressor="qgenx", quant=QuantConfig(num_levels=15, bucket_size=64)))
    st = ex.init_state()
    # plain tuples flatten to the same positional keys "0".."k" the old
    # 4-field (pre-EF) and 5-field (pre-pending) ExchangeState produced
    legacy_states = {
        "pre_error": (st.levels, st.levels_lo, st.hist, st.step),
        "pre_pending": (st.levels, st.levels_lo, st.hist, st.step, st.error),
    }
    for tag, legacy_st in legacy_states.items():
        d = str(tmp_path / tag)
        legacy = {"params": _trees()["params"], "ex_state": legacy_st}
        checkpointing.save(d, 7, legacy)
        templates = {"params": _trees()["params"], "ex_state": st}
        with pytest.raises(checkpointing.CheckpointStructureError) as ei:
            checkpointing.restore_with_fallback(d, templates)
        assert ei.value.tree == "ex_state" and "keys differ" in ei.value.detail
        step, trees, reset = checkpointing.restore_with_fallback(
            d, templates, allow_reset=("ex_state",))
        assert step == 7 and reset == ("ex_state",) and "ex_state" not in trees
        np.testing.assert_array_equal(np.asarray(trees["params"]["w"]),
                                      np.ones((4, 3), np.float32))


def test_bounded_retry(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3, 4):
        checkpointing.save(d, s, _trees(float(s)))
        faults.inject_ckpt_fault(d, s, "ckpt_truncate")
    with pytest.raises(checkpointing.CheckpointCorruptError):
        checkpointing.restore_with_fallback(d, _trees(), max_retries=3)
    # step 1 is intact again -> reachable only with enough retries
    checkpointing.save(d, 1, _trees(1.0))
    step, _, _ = checkpointing.restore_with_fallback(d, _trees(),
                                                     max_retries=4)
    assert step == 1


def test_atomic_write_leaves_no_partial_files(tmp_path):
    d = str(tmp_path)
    checkpointing.save(d, 1, _trees())
    assert not [fn for fn in os.listdir(d) if fn.endswith(".tmp")]
    assert checkpointing.available_steps(d) == [1]


# ---------------------------------------------------------------------------
# End-to-end: interrupted save -> fallback restore -> same loss
# ---------------------------------------------------------------------------

_TRAIN_ARGS = [
    "--arch", "tinyllama-1.1b", "--reduced",
    "--batch", "2", "--seq", "16", "--lr", "1e-3",
    "--optimizer", "adam", "--log-every", "10", "--seed", "3",
]


def test_interrupted_save_resumes_to_same_loss(tmp_path):
    """Truncate the newest checkpoint mid-'write' via the fault injector:
    the resumed run must fall back to step N-1 and land on the SAME final
    loss as an uninterrupted run (the synthetic pipeline is step-indexed
    deterministic, so state@2 + steps 2..6 is path-independent)."""
    from repro.launch import train

    clean = train.main(_TRAIN_ARGS + ["--steps", "6"])

    d = str(tmp_path / "ckpt")
    # phase 1: train to 4, checkpointing at 2 and 4 — but the step-4 save
    # (both the periodic one and the final one) is torn by the injector
    train.main(_TRAIN_ARGS + [
        "--steps", "4", "--checkpoint-dir", d, "--checkpoint-every", "2",
        "--fault-spec", "ckpt_truncate@4",
    ])
    assert checkpointing.latest_step(d) == 4  # pointer says 4...
    with pytest.raises(checkpointing.CheckpointCorruptError):
        checkpointing.restore(d, {}, step=4)  # ...but 4 is torn

    # phase 2: resume -> walks back to the intact step-2 checkpoint
    resumed = train.main(_TRAIN_ARGS + [
        "--steps", "6", "--checkpoint-dir", d, "--checkpoint-every", "2",
    ])
    assert resumed is not None
    assert abs(resumed - clean) < 1e-6, (resumed, clean)


def test_incompatible_checkpoint_exits_with_named_tree(tmp_path, capsys):
    """A checkpoint from a different run config must exit(2) naming the
    mismatched tree — not silently reset (unless --allow-ckpt-reset)."""
    from repro.launch import train

    d = str(tmp_path / "ckpt")
    checkpointing.save(d, 2, {
        "params": {"nothing": jnp.zeros((2,), jnp.float32)},
        "opt_state": {"m": jnp.zeros((2,), jnp.float32)},
        "ex_state": {"z": jnp.zeros((2,), jnp.float32)},
    })
    with pytest.raises(SystemExit) as ei:
        train.main(_TRAIN_ARGS + ["--steps", "4", "--checkpoint-dir", d])
    assert ei.value.code == 2
    err = capsys.readouterr().err
    assert "'params'" in err and "--allow-ckpt-reset" in err


def test_guard_watchdog_rolls_back(capsys):
    """Persistent NaN faults: the traced guard rejects every poisoned
    step and the host watchdog rolls back to the last-known-good
    snapshot after --rollback-after consecutive rejections."""
    from repro.launch import train

    loss = train.main(_TRAIN_ARGS + [
        "--steps", "7", "--guard", "--rollback-after", "2",
        "--fault-spec", "nan_grad@3-5", "--log-every", "1",
    ])
    out = capsys.readouterr().out
    assert "REJECTED" in out
    assert "watchdog: rolled back" in out
    assert "rejected=3" in out and "rollbacks=1" in out
    assert loss is not None and np.isfinite(loss)
