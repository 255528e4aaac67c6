"""Bucketed overlapped exchange (PR 9).

What this harness pins down, single-device (the 8-device semantics live
in tests/_multidev_bucketed.py):

1. PARTITION INVARIANTS — ``partition_leaf_ids`` emits contiguous,
   covering, layer-ordered buckets, exactly ``min(k, n_leaves)`` of
   them, deterministically.
2. NB=1/OFF PARITY GRID — ``num_buckets=1, overlap='off'`` is literally
   the pre-PR-9 exchange: the config equals the default config
   (same cached Exchange) and the traced jaxpr is byte-identical,
   across compressor x bits{4,8} x mode{gather,two_phase}.
3. BUCKETED == PER-BUCKET ORACLE — the fused bucketed exchange equals
   running a monolithic planned exchange per bucket with
   ``fold_in(key, bucket_index)``, bit-exactly.
4. WIRE ACCOUNTING — the trace-time recorder's ``b{i}/``-prefixed
   entries sum per bucket to ``bucket_wire_bytes_tree`` and in total to
   ``wire_bytes_tree``.
5. DEFER_TAIL STALENESS — step N applies step N-1's tail-bucket mean
   (zeros at N=0) and carries this sync's in ``state.pending``;
   checkpoint round-trips preserve ``pending`` bit-exactly.
6. LOUDNESS — every invalid combination (EF + overlap, overlap without
   buckets, buckets without overlap, leafwise/planless overlap,
   defer_tail + mask, placeholder pending) fails with a pointed error.
"""

import dataclasses
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import exchange_plan as xplan
from repro.core.exchange import (
    ExchangeConfig,
    make_exchange,
    wire_trace_start,
    wire_trace_stop,
)
from repro.core.quantization import QuantConfig

KEY = jax.random.PRNGKey(5)


def _one_dev_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _tree():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return {
        "emb": jax.random.normal(ks[0], (64, 16), jnp.float32),
        "h0": {"w": jax.random.normal(ks[1], (33, 31), jnp.float32),
               "b": jax.random.normal(ks[2], (31,), jnp.float32)},
        "head": jax.random.normal(ks[3], (16, 77), jnp.float32),
    }


def _cfg(bits=8, mode="gather", **kw):
    return ExchangeConfig(
        compressor=kw.pop("compressor", "qgenx"),
        quant=QuantConfig(num_levels=5 if bits == 4 else 15, bits=bits,
                          q_norm=math.inf, bucket_size=64),
        mode=mode, axis_name="data", **kw,
    )


def _run_tree(ex, tree, key, state=None):
    mesh = _one_dev_mesh()
    st = ex.init_state() if state is None else state

    def f(t, k):
        return ex.pmean_tree(t, st, k)

    specs = jax.tree_util.tree_map(lambda _: P(), tree)
    st_specs = jax.tree_util.tree_map(lambda _: P(), st)
    with mesh:
        out, new_st = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(specs, P()),
            out_specs=(specs, st_specs), check_vma=False,
        ))(tree, key)
    return out, new_st


# ---------------------------------------------------------------------------
# 1. partition invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sizes,k", [
    ((1024, 1023, 31, 1232, 77, 5), 3),
    ((10, 10, 10, 10), 4),
    ((5000, 1, 1, 1), 2),
    ((7,), 4),               # k > n_leaves clamps
    ((3, 3, 3), 8),          # k > n_leaves clamps
    (tuple(range(1, 40)), 8),
])
def test_partition_invariants(sizes, k):
    buckets = xplan.partition_leaf_ids(sizes, k)
    # exactly min(k, n) buckets, each non-empty
    assert len(buckets) == min(k, len(sizes))
    assert all(b for b in buckets)
    # contiguous, layer-ordered, covering
    flat = [i for b in buckets for i in b]
    assert flat == list(range(len(sizes)))
    # deterministic (and lru-cache-hit) on repeat
    assert xplan.partition_leaf_ids(sizes, k) is buckets


def test_partition_is_size_balanced():
    sizes = (100, 100, 100, 100, 100, 100, 100, 100)
    buckets = xplan.partition_leaf_ids(sizes, 4)
    assert [len(b) for b in buckets] == [2, 2, 2, 2]


# ---------------------------------------------------------------------------
# 2. nb=1/off parity grid: identical config -> identical cached Exchange
#    -> byte-identical jaxpr with the pre-PR-9 default path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("compressor", ["qgenx", "layerwise", "none"])
@pytest.mark.parametrize("mode", ["gather", "two_phase"])
@pytest.mark.parametrize("bits", [8, 4])
def test_nb1_off_is_the_pr5_path(compressor, bits, mode):
    explicit = _cfg(bits, mode, compressor=compressor,
                    num_buckets=1, overlap="off")
    default = _cfg(bits, mode, compressor=compressor)
    assert explicit == default
    ex_e, ex_d = make_exchange(explicit), make_exchange(default)
    assert ex_e is ex_d  # same frozen config -> same cached instance

    tree = _tree()
    mesh = _one_dev_mesh()

    def mk(ex):
        st = ex.init_state()

        def f(t, k):
            return ex.pmean_tree(t, st, k)

        specs = jax.tree_util.tree_map(lambda _: P(), tree)
        st_specs = jax.tree_util.tree_map(lambda _: P(), st)
        with mesh:
            return str(jax.make_jaxpr(shard_map(
                f, mesh=mesh, in_specs=(specs, P()),
                out_specs=(specs, st_specs), check_vma=False,
            ))(tree, KEY))

    assert mk(ex_e) == mk(ex_d)
    # and the results agree bitwise, not just the program text
    got, _ = _run_tree(ex_e, tree, KEY)
    want, _ = _run_tree(ex_d, tree, KEY)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 3. bucketed == per-bucket monolithic oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["gather", "two_phase"])
@pytest.mark.parametrize("nb", [2, 3])
def test_bucketed_matches_per_bucket_oracle(nb, mode):
    tree = _tree()
    cfg = _cfg(8, mode, num_buckets=nb, overlap="bucketed")
    ex = make_exchange(cfg)
    ex_mono = make_exchange(_cfg(8, mode))

    got, _ = _run_tree(ex, tree, KEY)

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buckets = ex.compressor.bucket_partition(leaves, cfg)
    assert len(buckets) == nb
    oracle = [None] * len(leaves)
    for bi, ids in enumerate(buckets):
        sub = [leaves[i] for i in ids]
        mean, _ = _run_tree(ex_mono, sub, jax.random.fold_in(KEY, bi))
        for i, m in zip(ids, mean):
            oracle[i] = m
    want = jax.tree_util.tree_unflatten(treedef, oracle)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 4. per-bucket recorder == analytic wire accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_bucket_wire_recorder_matches_analytic(bits):
    tree = _tree()
    cfg = _cfg(bits, "gather", num_buckets=3, overlap="bucketed")
    ex = make_exchange(cfg)

    wire_trace_start()
    _run_tree(ex, tree, KEY)
    rec = wire_trace_stop()

    per_bucket = {}
    for name, b in rec:
        assert name.startswith("b"), name  # every operand is prefixed
        bi = int(name.split("/")[0][1:])
        per_bucket[bi] = per_bucket.get(bi, 0.0) + b
    want = ex.bucket_wire_bytes_tree(tree, axis_size=1)
    assert len(per_bucket) == len(want) == 3
    for bi, w in enumerate(want):
        assert per_bucket[bi] == w, (bi, per_bucket, want)
    assert sum(per_bucket.values()) == ex.wire_bytes_tree(tree, 1)


# ---------------------------------------------------------------------------
# 5. defer_tail staleness + pending round-trip
# ---------------------------------------------------------------------------


def test_defer_tail_two_step_staleness():
    tree = _tree()
    cfg = _cfg(8, "gather", num_buckets=2, overlap="defer_tail")
    ex = make_exchange(cfg)
    ex_b = make_exchange(_cfg(8, "gather", num_buckets=2, overlap="bucketed"))

    leaves, _ = jax.tree_util.tree_flatten(tree)
    tail_ids = set(ex.compressor.bucket_partition(leaves, cfg)[0])

    st0 = ex.init_state(template=tree, num_workers=1)
    assert st0.pending.ndim == 1 and st0.pending.shape[0] > 1
    assert not np.any(np.asarray(st0.pending))

    k0, k1 = jax.random.PRNGKey(21), jax.random.PRNGKey(22)
    out0, st1 = _run_tree(ex, tree, k0, state=st0)
    out1, st2 = _run_tree(ex, tree, k1, state=st1)
    ref0, _ = _run_tree(ex_b, tree, k0)
    ref1, _ = _run_tree(ex_b, tree, k1)

    for i, (a0, a1, r0, r1) in enumerate(zip(
        *(jax.tree_util.tree_leaves(t) for t in (out0, out1, ref0, ref1))
    )):
        a0, a1, r0, r1 = (np.asarray(x) for x in (a0, a1, r0, r1))
        if i in tail_ids:
            # step 0 applies the zero-initialized pending; step 1 applies
            # step 0's tail mean — exactly the non-deferred run under k0
            assert not np.any(a0), i
            np.testing.assert_array_equal(a1, r0)
        else:
            # non-tail buckets are never deferred
            np.testing.assert_array_equal(a0, r0)
            np.testing.assert_array_equal(a1, r1)
    # pending after step 1 is THIS sync's tail mean, not the applied one
    assert not np.array_equal(np.asarray(st1.pending), np.asarray(st2.pending))


def test_defer_tail_pending_checkpoint_roundtrip():
    from repro.checkpoint.checkpointing import restore, save

    tree = _tree()
    ex = make_exchange(_cfg(8, "gather", num_buckets=2, overlap="defer_tail"))
    st = ex.init_state(template=tree, num_workers=1)
    _, st = _run_tree(ex, tree, KEY, state=st)
    assert np.any(np.asarray(st.pending))  # nonzero payload round-trips

    with tempfile.TemporaryDirectory() as td:
        save(td, 1, {"ex_state": st})
        got_step, trees = restore(td, {"ex_state": st})
    assert got_step == 1
    np.testing.assert_array_equal(np.asarray(trees["ex_state"].pending),
                                  np.asarray(st.pending))


# ---------------------------------------------------------------------------
# 6. loud rejections
# ---------------------------------------------------------------------------


def test_buckets_without_overlap_rejected():
    with pytest.raises(ValueError, match="overlap"):
        _cfg(8, "gather", num_buckets=4, overlap="off")


def test_overlap_without_buckets_rejected():
    with pytest.raises(ValueError, match="num_buckets"):
        _cfg(8, "gather", num_buckets=1, overlap="bucketed")


def test_unknown_overlap_rejected():
    with pytest.raises(ValueError, match="overlap"):
        _cfg(8, "gather", num_buckets=2, overlap="async")


def test_leafwise_overlap_rejected():
    with pytest.raises(ValueError, match="leafwise"):
        _cfg(8, "leafwise", num_buckets=2, overlap="bucketed")


def test_planless_overlap_rejected():
    with pytest.raises(ValueError, match="use_plan"):
        _cfg(8, "gather", num_buckets=2, overlap="bucketed", use_plan=False)


@pytest.mark.parametrize("name,kw", [
    ("ef21-topk", {"ef_topk_frac": 0.25}),
    ("ef-randk", {"rand_frac": 0.25}),
])
def test_error_feedback_overlap_rejected(name, kw):
    cfg = ExchangeConfig(compressor=name, axis_name="data",
                         num_buckets=2, overlap="bucketed", **kw)
    with pytest.raises(ValueError, match="error"):
        make_exchange(cfg)


def test_defer_tail_mask_rejected():
    tree = _tree()
    ex = make_exchange(_cfg(8, "gather", num_buckets=2, overlap="defer_tail"))
    st = ex.init_state(template=tree, num_workers=1)
    mesh = _one_dev_mesh()
    specs = jax.tree_util.tree_map(lambda _: P(), tree)

    def f(t, k, m):
        return ex.pmean_tree(t, st, k, mask=m)[0]

    with pytest.raises(ValueError, match="mask"):
        with mesh:
            jax.jit(shard_map(
                f, mesh=mesh, in_specs=(specs, P(), P()),
                out_specs=specs, check_vma=False,
            ))(tree, KEY, jnp.ones((), jnp.float32))


def test_defer_tail_placeholder_pending_rejected():
    """A defer_tail exchange fed a state built without
    ``init_state(template=..., num_workers=...)`` must fail at trace time
    with a pointer at the fix, not a silent shape blow-up."""
    tree = _tree()
    ex = make_exchange(_cfg(8, "gather", num_buckets=2, overlap="defer_tail"))
    with pytest.raises(ValueError, match="init_state"):
        _run_tree(ex, tree, KEY, state=ex.init_state())
