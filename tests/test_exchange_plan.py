"""ExchangePlan — static flat-buffer exchange layout (DESIGN.md §1.5).

Pins the plan's contracts:

* **bit-exact parity** of the planned qgenx pmean_tree with the per-call
  (PR 4) path over the full (bits, mode, use_pallas) grid — same
  concatenation order, same padding semantics, same noise draws — and
  the same for the layerwise per-group exchange and randk;
* **layout invariants**: contiguous offsets in pack order, per-segment
  tile alignment, plan caching;
* the **segment-fused quantize∘dequantize** kernel against the
  per-segment block oracle (bit-exact under identical noise), Pallas
  interpret vs jnp reference;
* the planned ``compress_tree`` stays **unbiased** (the Definition 1
  contract the whole rate analysis rests on) while collapsing the
  per-leaf launch pairs into one fused invocation;
* the **documented wire-bytes delta**: a planned compression pays ONE
  shared padding tail per segment where the per-leaf path paid one per
  leaf — the accounting follows the emission exactly;
* the donation satellite: a train step jitted with ALL carried state
  donated (params/opt_state/ex_state) runs, and ex_state round-trips
  through checkpoint save/restore.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import exchange_plan as xplan
from repro.core.exchange import ExchangeConfig, make_exchange
from repro.core.quantization import QuantConfig, uniform_levels

KEY = jax.random.PRNGKey(11)


def _one_dev_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _tree():
    # mixed sizes: none a bucket multiple (exercises padding), one leaf
    # above and several below the layerwise threshold used below
    return {
        "emb": jax.random.normal(jax.random.PRNGKey(0), (100, 40), jnp.float32),
        "w": jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32),
        "b": jax.random.normal(jax.random.PRNGKey(2), (77,), jnp.float32),
    }


def _run_pmean_tree(ex, tree, key=KEY):
    mesh = _one_dev_mesh()
    specs = {k: P() for k in tree}

    @jax.jit
    def go(t, k):
        def f(tl, kk):
            mean, _ = ex.pmean_tree(tl, ex.init_state(), kk)
            return mean

        return shard_map(f, mesh=mesh, in_specs=(specs, P()),
                         out_specs=specs, check_vma=False)(t, k)

    return go(tree, key)


# ---------------------------------------------------------------------------
# Parity grid: planned == per-call, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("mode", ["gather", "two_phase"])
@pytest.mark.parametrize("bits", [8, 4])
def test_qgenx_plan_parity_grid(bits, mode, use_pallas):
    """The acceptance grid: the planned qgenx tree exchange is bit-exact
    with the per-call path (same buffer, same keys, same collectives)."""
    quant = QuantConfig(num_levels=5 if bits == 4 else 15, bits=bits,
                        bucket_size=256, q_norm=math.inf)
    cfg = ExchangeConfig(compressor="qgenx", quant=quant, mode=mode,
                         axis_name="data", use_pallas=use_pallas)
    tree = _tree()
    planned = _run_pmean_tree(make_exchange(cfg), tree)
    legacy = _run_pmean_tree(
        make_exchange(dataclasses.replace(cfg, use_plan=False)), tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(planned[k]),
                                      np.asarray(legacy[k]))


@pytest.mark.parametrize("mode", ["gather", "two_phase"])
def test_layerwise_plan_parity(mode):
    """Per-layer policies as segments of ONE buffer: group order, per-
    group padding and per-group keys match the per-call path exactly."""
    cfg = ExchangeConfig(
        compressor="layerwise",
        quant=QuantConfig(num_levels=5, bits=4, bucket_size=256),
        layerwise_threshold=1024, mode=mode, axis_name="data",
    )
    tree = _tree()
    planned = _run_pmean_tree(make_exchange(cfg), tree)
    legacy = _run_pmean_tree(
        make_exchange(dataclasses.replace(cfg, use_plan=False)), tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(planned[k]),
                                      np.asarray(legacy[k]))


def test_randk_plan_parity():
    """The unquantized-segment plan packs exactly the legacy flat concat."""
    cfg = ExchangeConfig(compressor="randk", rand_frac=0.25, mode="gather",
                         axis_name="data")
    tree = _tree()
    planned = _run_pmean_tree(make_exchange(cfg), tree)
    legacy = _run_pmean_tree(
        make_exchange(dataclasses.replace(cfg, use_plan=False)), tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(planned[k]),
                                      np.asarray(legacy[k]))


def test_coded_bits_plan_parity():
    """The Theorem-2 metric over the planned buffer equals the
    concat+pad path it replaced (same bucket-padded coordinates)."""
    cfg = ExchangeConfig(compressor="qgenx",
                         quant=QuantConfig(num_levels=15, bucket_size=256),
                         mode="gather", axis_name="data")
    tree = _tree()
    ex = make_exchange(cfg)
    ex_legacy = make_exchange(dataclasses.replace(cfg, use_plan=False))
    a = float(ex.coded_bits_tree(tree, ex.init_state()))
    b = float(ex_legacy.coded_bits_tree(tree, ex_legacy.init_state()))
    assert a == b


# ---------------------------------------------------------------------------
# Layout invariants
# ---------------------------------------------------------------------------


def test_plan_layout_offsets_and_alignment():
    cfg = ExchangeConfig(
        compressor="layerwise",
        quant=QuantConfig(num_levels=5, bits=4, bucket_size=256),
        layerwise_threshold=1024, mode="gather", axis_name="data",
    )
    ex = make_exchange(cfg)
    tree = _tree()
    plan = ex.plan_for_tree(tree, axis_size=1, purpose="pmean")
    leaves = jax.tree_util.tree_leaves(tree)
    # big group (emb 4000, w 2048) first, then small (b 77); offsets are
    # contiguous within each segment, in pack order
    assert len(plan.segments) == 2
    seg_big, seg_small = plan.segments
    assert seg_big.table == 1 and seg_small.table == 0
    assert seg_big.n == 4000 + 2048 and seg_small.n == 77
    for seg in plan.segments:
        assert seg.padded % seg.quant.bucket_size == 0
        assert seg.padded >= seg.n
        pos = seg.start
        for i in seg.leaf_ids:
            assert plan.offsets[i] == pos
            pos += leaves[i].size
    assert plan.total == sum(s.padded for s in plan.segments)
    assert plan.n_live == sum(l.size for l in leaves)
    # pack round-trips through unpack
    flat = plan.pack(leaves)
    assert flat.shape == (plan.total,)
    back = plan.unpack(flat, leaves)
    for l, r in zip(leaves, back):
        np.testing.assert_array_equal(np.asarray(l), np.asarray(r))
    # padding tails are zero
    tail = np.asarray(flat[seg_big.start + seg_big.n: seg_big.stop])
    assert not tail.any()


def test_plan_two_phase_quota_alignment():
    """Two-phase segments pad to the axis_size*bucket chunk quota — the
    exact padding _qgenx_pmean would have applied downstream."""
    quant = QuantConfig(num_levels=15, bucket_size=256)
    cfg = ExchangeConfig(compressor="qgenx", quant=quant, mode="two_phase",
                         axis_name="data")
    ex = make_exchange(cfg)
    plan = ex.plan_for_tree(_tree(), axis_size=8, purpose="pmean")
    (seg,) = plan.segments
    assert seg.padded % (8 * quant.bucket_size) == 0
    assert seg.padded - seg.n < 8 * quant.bucket_size


def test_plan_is_cached():
    cfg = ExchangeConfig(compressor="qgenx",
                         quant=QuantConfig(num_levels=15, bucket_size=256),
                         mode="gather", axis_name="data")
    ex = make_exchange(cfg)
    t = _tree()
    assert ex.plan_for_tree(t) is ex.plan_for_tree(t)  # lru-cached layout


# ---------------------------------------------------------------------------
# Segment-fused kernel: Pallas vs reference vs per-segment oracle
# ---------------------------------------------------------------------------


def test_segment_fused_kernel_matches_per_segment_oracle():
    from repro.kernels.ref import (
        dequantize_blocks_ref,
        quantize_blocks_ref,
        quantize_dequantize_segments_ref,
    )
    from repro.kernels.segment_quantize import quantize_dequantize_segments

    bucket, nb = 256, 11  # odd row count exercises the tile padding
    x = jax.random.normal(jax.random.PRNGKey(3), (nb, bucket), jnp.float32)
    noise = jax.random.uniform(jax.random.PRNGKey(4), (nb, bucket))
    lv_hi, lv_lo = uniform_levels(15), uniform_levels(5)
    tables, nsym = xplan.stack_level_tables([lv_hi, lv_lo])
    seg = jnp.asarray([0] * 6 + [1] * 5, jnp.int32)

    fused = quantize_dequantize_segments_ref(
        x, noise, tables, seg, num_symbols=nsym, q_is_inf=True)
    # segment-by-segment block oracle under the SAME noise rows
    for (a, b), lv in (((0, 6), lv_hi), ((6, 11), lv_lo)):
        idx, norms = quantize_blocks_ref(x[a:b], noise[a:b], lv, q_is_inf=True)
        want = dequantize_blocks_ref(idx, norms, lv)
        np.testing.assert_array_equal(np.asarray(fused[a:b]), np.asarray(want))
    # Pallas (interpret) == jnp reference, bit for bit
    got = quantize_dequantize_segments(
        x, noise, tables, seg, num_symbols=nsym, q_is_inf=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fused))


def test_segment_fused_device_prng_traces():
    """TPU-only path: no interpret-mode lowering on CPU, but the lowering
    contract (shapes, no host noise buffer) is trace-checked."""
    from repro.kernels.segment_quantize import quantize_dequantize_segments

    bucket, nb = 256, 8
    x = jnp.zeros((nb, bucket), jnp.float32)
    tables, nsym = xplan.stack_level_tables([uniform_levels(15)])
    f = functools.partial(
        quantize_dequantize_segments, num_symbols=nsym, q_is_inf=True,
        use_device_prng=True,
    )
    out = jax.eval_shape(
        lambda a, t, s, sd: f(a, None, t, s, seed=sd),
        x, tables, jnp.zeros((nb,), jnp.int32), jnp.zeros((1,), jnp.int32),
    )
    assert out.shape == (nb, bucket)


# ---------------------------------------------------------------------------
# Planned compression: unbiasedness contract + fused launch count
# ---------------------------------------------------------------------------


def _compress_cfg(name):
    if name == "qgenx":
        return ExchangeConfig(
            compressor="qgenx",
            quant=QuantConfig(num_levels=15, bucket_size=256), mode="gather",
            axis_name="data")
    return ExchangeConfig(
        compressor="layerwise",
        quant=QuantConfig(num_levels=5, bits=4, bucket_size=256),
        layerwise_threshold=1024, mode="gather", axis_name="data")


@pytest.mark.parametrize("name", ["qgenx", "layerwise"])
def test_planned_compress_tree_unbiased(name):
    """E[compress_tree(v)] = v under the plan — the segment-fused path
    keeps the Definition 1 contract (different noise partitioning than
    per-leaf, same expectation)."""
    ex = make_exchange(_compress_cfg(name))
    tree = _tree()
    trials = 768
    keys = jax.random.split(jax.random.PRNGKey(5), trials)
    outs = jax.vmap(lambda k: ex.compress_tree(tree, k))(keys)
    for k in tree:
        est = np.asarray(jnp.mean(outs[k], axis=0))
        std = np.asarray(jnp.std(outs[k], axis=0))
        err = np.abs(est - np.asarray(tree[k]))
        tol = 5.0 * std / math.sqrt(trials) + 1e-6
        frac_bad = float(np.mean(err > tol))
        assert frac_bad < 0.01, (name, k, frac_bad)


def test_planned_compress_is_one_fused_invocation():
    """With use_pallas the planned compress_tree lowers to exactly ONE
    segment-fused kernel launch for the whole (single-policy) pytree;
    the per-leaf path lowers none (pure-jnp chains, one per leaf)."""
    cfg = dataclasses.replace(_compress_cfg("qgenx"), use_pallas=True)
    tree = _tree()
    ex = make_exchange(cfg)
    text = str(jax.make_jaxpr(lambda t, k: ex.compress_tree(t, k))(tree, KEY))
    # a kernel is staged once per platform branch (Mosaic on a TPU, the
    # interpreter elsewhere): count the launches a TPU runs
    assert text.count("interpret=False") == 1
    ex_legacy = make_exchange(dataclasses.replace(cfg, use_plan=False))
    legacy = str(jax.make_jaxpr(
        lambda t, k: ex_legacy.compress_tree(t, k))(tree, KEY))
    assert "pallas_call" not in legacy  # per-leaf path: N jnp launch pairs


# ---------------------------------------------------------------------------
# Wire accounting: the documented delta
# ---------------------------------------------------------------------------


def test_compress_wire_bytes_shared_tail_delta():
    """A planned compression pays ONE padding tail per segment; the
    per-leaf path pays one per leaf.  The delta is exactly the saved
    per-leaf bucket ceils — never silently absorbed."""
    cfg = _compress_cfg("qgenx")
    q = cfg.quant
    ex = make_exchange(cfg)
    ex_legacy = make_exchange(dataclasses.replace(cfg, use_plan=False))
    tree = _tree()
    leaves = jax.tree_util.tree_leaves(tree)

    planned = ex.compress_wire_bytes_tree(tree)
    legacy = ex_legacy.compress_wire_bytes_tree(tree)
    n_live = sum(l.size for l in leaves)
    assert planned == float(q.payload_bytes(n_live))  # one shared tail
    assert legacy == float(sum(q.payload_bytes(l.size) for l in leaves))
    assert planned <= legacy
    # this tree's leaf sizes don't bucket-align -> strict saving
    assert planned < legacy


def test_pmean_wire_accounting_unchanged_by_plan():
    """The pmean exchange moves the SAME collective operands planned or
    not (the plan's tail is the pad the exchange applied anyway): the
    trace recorder totals agree with the analytic accounting for both."""
    import repro.core.exchange as exchange_mod

    tree = _tree()
    for use_plan in (True, False):
        cfg = ExchangeConfig(
            compressor="qgenx",
            quant=QuantConfig(num_levels=15, bucket_size=256),
            mode="two_phase", axis_name="data", use_plan=use_plan)
        ex = make_exchange(cfg)
        exchange_mod.wire_trace_start()
        _run_pmean_tree(ex, tree)
        rec = exchange_mod.wire_trace_stop()
        assert sum(b for _, b in rec) == ex.wire_bytes_tree(tree, 1), (
            use_plan, rec)


# ---------------------------------------------------------------------------
# Donation satellite: all carried state donated + checkpoint round-trip
# ---------------------------------------------------------------------------


def test_train_step_donates_all_state_and_checkpoints(tmp_path):
    """The train CLI jits with donate_argnums=(0, 1, 2) — params,
    opt_state AND ex_state.  The donated step must run repeatedly (every
    output has the input's structure) and the ExchangeState must
    round-trip through checkpoint save/restore."""
    from repro.checkpoint import checkpointing
    from repro.configs.registry import get_config
    from repro.launch.steps import make_train_step
    from repro.models.model import build
    from repro.optim import optimizers as opt

    mcfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                               dtype="float32")
    model = build(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="extra_adam", lr=1e-3)
    opt_state = opt.init_state(opt_cfg, params)
    ex_cfg = ExchangeConfig(
        compressor="qgenx", quant=QuantConfig(num_levels=15, bucket_size=256),
        mode="gather", axis_name="data", level_schedule="qada",
        level_update_every=1)
    mesh = _one_dev_mesh()
    step = make_train_step(model, opt_cfg, exchange=ex_cfg, mesh=mesh)
    ex = make_exchange(ex_cfg)
    ex_state = ex.init_state()
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
             "labels": jnp.zeros((4, 16), jnp.int32)}

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    with mesh:
        for i in range(2):  # second call consumes donated outputs
            params, opt_state, ex_state, metrics = jitted(
                params, opt_state, ex_state, batch, jax.random.PRNGKey(i))
    assert np.isfinite(float(metrics["loss"]))
    assert int(ex_state.step) == 4  # 2 steps x 2 exchanges, qada refreshed

    ckpt = str(tmp_path / "ckpt")
    checkpointing.save(ckpt, 2, {"params": params, "opt_state": opt_state,
                                 "ex_state": ex_state})
    _, trees = checkpointing.restore(
        ckpt, {"params": params, "opt_state": opt_state,
               "ex_state": ex_state})
    for a, b in zip(jax.tree_util.tree_leaves(trees["ex_state"]),
                    jax.tree_util.tree_leaves(ex_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the restored state keeps driving the donated step
    with mesh:
        out = jitted(params, opt_state, trees["ex_state"], batch,
                     jax.random.PRNGKey(9))
    assert int(out[2].step) == 6
