"""The Theorem 2 entropy-coded wire estimate (metrics["coded_bits_est"]).

The traced estimate in ``repro.core.exchange`` must agree with the
host-side numpy oracle in ``repro.core.coding`` (the bit-exact codec
module) on the same pmf, lower-bound the fixed-width payload actually
shipped (8-bit configs: provable; 4-bit: checked on gradient-like data),
and ride through the train step with the same per-call × n_calls
semantics as ``wire_bytes``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding
from repro.core.exchange import (
    ExchangeConfig,
    expected_index_pmf,
    make_exchange,
    theorem2_bits_traced,
)
from repro.core.quantization import QuantConfig, uniform_levels

KEY = jax.random.PRNGKey(5)


def _pmf(x, quant):
    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant))
    state = ex.init_state()
    from repro.core.quantization import _pad_to_buckets, bucket_norms

    v2d, _ = _pad_to_buckets(x.reshape(-1).astype(jnp.float32),
                             quant.bucket_size)
    norms = bucket_norms(v2d, quant.q_norm)
    safe = jnp.where(norms > 0, norms, 1.0)
    u = jnp.clip(jnp.abs(v2d) / safe[:, None], 0.0, 1.0)
    return expected_index_pmf(u, state.levels), v2d.shape[0]


def test_pmf_is_a_distribution():
    quant = QuantConfig(num_levels=15, bucket_size=256)
    x = jax.random.normal(KEY, (3000,), jnp.float32)
    pmf, _ = _pmf(x, quant)
    assert pmf.shape == (quant.num_symbols,)
    assert float(jnp.sum(pmf)) == np.float32(1.0) or np.isclose(
        float(jnp.sum(pmf)), 1.0, atol=1e-5
    )
    assert float(jnp.min(pmf)) >= 0.0


def test_traced_formula_matches_coding_oracle():
    """reuse of core/coding.py: the traced Theorem-2 estimate equals the
    numpy ``theorem2_expected_bits`` on the same pmf, d, bucket count."""
    quant = QuantConfig(num_levels=15, bucket_size=256)
    x = jax.random.normal(KEY, (2000,), jnp.float32)
    pmf, nb = _pmf(x, quant)
    d = nb * quant.bucket_size
    got = float(theorem2_bits_traced(pmf, d, nb))
    want = coding.theorem2_expected_bits(np.asarray(pmf), d, num_buckets=nb)
    assert np.isclose(got, want, rtol=1e-5), (got, want)


def test_coded_estimate_lower_bounds_fixed_width_int8():
    """For 8-bit payloads the Theorem-2 bound is ALWAYS below the
    fixed-width bits ((H+1) + sign <= log2(17)+2 < 8), so the estimate
    must lower-bound 8 * payload_bytes on any input."""
    quant = QuantConfig(num_levels=15, bucket_size=256)
    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant))
    state = ex.init_state()
    for seed, scale in ((0, 1.0), (1, 100.0), (2, 1e-4)):
        x = scale * jax.random.normal(jax.random.PRNGKey(seed), (3000,))
        coded = float(ex.coded_bits_tree({"w": x}, state))
        fixed_bits = 8.0 * quant.payload_bytes(3000)
        assert 0.0 < coded < fixed_bits, (seed, coded, fixed_bits)


def test_coded_estimate_lower_bounds_fixed_width_int4_gradients():
    """4-bit: not a worst-case theorem (an L-inf-normalized gaussian can
    exceed the nibble — entropy ~log2(7) plus the +1-bit code overhead),
    but under QSGD-style L2 bucket norms (normalized magnitudes
    concentrate near zero, the low symbols dominate) the entropy code
    beats the fixed-width nibble.  (The estimate exceeding fixed width in
    the L-inf case is the metric doing its job: it shows when CODE o Q
    would NOT pay.)"""
    quant = QuantConfig(num_levels=5, bits=4, bucket_size=256, q_norm=2.0)
    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant))
    state = ex.init_state()
    x = jax.random.normal(KEY, (4096,), jnp.float32)
    coded = float(ex.coded_bits_tree({"w": x}, state))
    fixed_bits = 8.0 * quant.payload_bytes(4096)
    assert 0.0 < coded < fixed_bits, (coded, fixed_bits)


def test_non_qgenx_compressors_report_zero():
    # none/randk code no indices; layerwise would need per-group pmfs
    # against both level tables (see Exchange.coded_bits_tree docstring)
    for name, kw in (("none", {}), ("randk", {}),
                     ("layerwise",
                      {"quant": QuantConfig(num_levels=5, bits=4,
                                            bucket_size=256)})):
        ex = make_exchange(ExchangeConfig(compressor=name, **kw))
        assert float(ex.coded_bits_tree(
            {"w": jnp.ones((64,))}, ex.init_state())) == 0.0


def test_metric_rides_through_train_step_with_n_calls_semantics():
    """metrics["coded_bits_est"] > 0 for a level-table compressor, equals
    per-call estimate x exchanges performed, and is 0 on local steps of
    the sync_every regime (mirrors wire_bytes)."""
    from jax.sharding import Mesh

    from repro.configs.registry import get_config
    from repro.launch.steps import make_train_step
    from repro.models.model import build
    from repro.optim import optimizers as opt

    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = opt.OptimizerConfig(name="extra_adam", lr=1e-3)
    ex = make_exchange(ExchangeConfig(
        compressor="qgenx", quant=QuantConfig(num_levels=15, bucket_size=256),
        mode="gather", axis_name="data", sync_every=2,
    ))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    step = jax.jit(make_train_step(model, opt_cfg, exchange=ex, mesh=mesh))
    opt_state = opt.init_state(opt_cfg, params)
    ex_state = ex.init_state()
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
             "labels": jnp.zeros((4, 16), jnp.int32)}
    codeds = []
    with mesh:
        for t in range(2):
            params, opt_state, ex_state, m = step(
                params, opt_state, ex_state, batch, jax.random.fold_in(KEY, t)
            )
            codeds.append(float(m["coded_bits_est"]))
    assert codeds[0] == 0.0  # local step: nothing exchanged, nothing coded
    assert codeds[1] > 0.0  # sync step: 2 exchanges' worth of coded bits
    n = sum(l.size for l in jax.tree_util.tree_leaves(params))
    # sanity: the estimate is in the ballpark of the fixed-width payload
    # for TWO exchanges, and strictly below it (int8 bound)
    assert codeds[1] < 2 * 8.0 * ex.cfg.quant.payload_bytes(n)


def test_uniform_magnitudes_reach_top_symbol():
    """u == 1 coordinates round deterministically to the top level — the
    pmf must put their whole mass on the last symbol (searchsorted-edge
    regression for the compare-accumulate construction)."""
    lv = uniform_levels(3)  # [0, .25, .5, .75, 1] -> num_symbols = 5
    pmf = expected_index_pmf(jnp.ones((128,), jnp.float32), lv)
    np.testing.assert_allclose(np.asarray(pmf),
                               np.asarray([0, 0, 0, 0, 1.0]), atol=1e-6)


# --- the one-pass estimate against a numpy oracle --------------------------

B = 512  # the cell's bucket


def _oracle_pmf(u, levels):
    """Numpy twin of Definition 1's expected rounding: each coordinate's
    bracket by searchsorted, mass 1 - xi down and xi up, by bincount."""
    u = np.asarray(u, np.float64).ravel()
    lv = np.asarray(levels, np.float64)
    tau = np.clip(np.searchsorted(lv, u, side="right") - 1, 0, len(lv) - 2)
    xi = np.clip((u - lv[tau]) / (lv[tau + 1] - lv[tau]), 0.0, 1.0)
    return (np.bincount(tau, 1.0 - xi, len(lv))
            + np.bincount(tau + 1, xi, len(lv))) / u.size


def _oracle_bits(tree, levels, quant):
    """Theorem 2 over the pmf of the concatenated, bucket-padded tree."""
    flat = np.concatenate([np.asarray(l, np.float32).ravel().astype(np.float64)
                           for l in jax.tree_util.tree_leaves(tree)])
    nb = -(-flat.size // quant.bucket_size)
    v = np.zeros(nb * quant.bucket_size)
    v[:flat.size] = flat
    a = np.abs(v.reshape(nb, quant.bucket_size))
    norm = (a.max(1) if math.isinf(quant.q_norm)
            else (a ** quant.q_norm).sum(1) ** (1 / quant.q_norm))
    u = np.clip(a / np.where(norm > 0, norm, 1.0)[:, None], 0.0, 1.0)
    pmf = _oracle_pmf(u, levels)
    return coding.theorem2_expected_bits(pmf, v.size, num_buckets=nb)


def _cell_miniature(key):
    """The cell's tree in small: a bucket-aligned f32 [R, 2b] leaf (one
    bucket row all zero), two 128-wide leaves that shift the rest off the
    bucket grid, bf16 matrices, and a 1-D leaf that leaves a padding
    tail."""
    k = jax.random.split(key, 5)
    embed = jax.random.normal(k[0], (6, 2 * B)) * jnp.arange(6.0)[:, None]
    return {
        "embed": embed,
        "k_norm": jax.random.normal(k[1], (1, 128)),
        "q_norm": jax.random.normal(k[2], (1, 128)),
        "wq": jax.random.normal(k[3], (1, 64, 4, 128)).astype(jnp.bfloat16),
        "wi": (jax.random.normal(k[4], (1, 64, 3 * B)) ** 3).astype(jnp.bfloat16),
        "z_scale": jnp.linspace(-1.0, 1.0, 300),
    }


def _warped_levels(num_symbols):
    """A strictly increasing, non-uniform table on [0, 1] (as QAda
    leaves them): denser near zero."""
    return jnp.linspace(0.0, 1.0, num_symbols) ** 2


QUANTS = {"int8": dict(num_levels=15, bits=8), "int4": dict(num_levels=5, bits=4)}


@pytest.mark.parametrize("use_plan", [True, False], ids=["plan", "concat"])
@pytest.mark.parametrize("table", ["uniform", "warped"])
@pytest.mark.parametrize("q_norm", [math.inf, 2.0], ids=["linf", "l2"])
@pytest.mark.parametrize("bits", ["int8", "int4"])
def test_coded_bits_matches_oracle_on_cell_layout(bits, q_norm, table,
                                                  use_plan):
    """In-place, packed and padding runs together price the same vector
    the numpy oracle concatenates and pads."""
    quant = QuantConfig(bucket_size=B, q_norm=q_norm, **QUANTS[bits])
    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant,
                                      use_plan=use_plan))
    state = ex.init_state()
    if table == "warped":
        state = dataclasses.replace(
            state, levels=_warped_levels(quant.num_symbols))
    tree = _cell_miniature(KEY)
    got = float(jax.jit(ex.coded_bits_tree)(tree, state))
    want = _oracle_bits(tree, state.levels, quant)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_cell_miniature_reads_the_aligned_leaf_in_place():
    """The layout rule on the miniature: the aligned leaf alone is read
    in place, and the rest is one packed run ending on the padding."""
    quant = QuantConfig(num_levels=15, bucket_size=B)
    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant))
    tree = _cell_miniature(KEY)
    leaves = jax.tree_util.tree_leaves(tree)
    _, total, runs = ex._coded_layout(leaves)
    assert [(r.in_place, r.ids) for r in runs] == [
        (True, (0,)), (False, (1, 2, 3, 4, 5))]
    assert runs[-1].stop == total and total % B == 0
    n = sum(l.size for l in leaves)
    assert ex.coded_in_place_share(tree) == pytest.approx(6 * 2 * B / n)


EDGES = {
    # u exactly on every level, interior and end points
    "on_levels": lambda lv: lv,
    "one": lambda lv: jnp.ones((64,)),
    "zero_bucket": lambda lv: jnp.zeros((B,)),
    "mixed": lambda lv: jnp.concatenate(
        [lv, jnp.zeros((7,)), jnp.ones((3,)), (lv[1:] + lv[:-1]) / 2]),
}


@pytest.mark.parametrize("table", ["uniform", "warped"])
@pytest.mark.parametrize("edge", sorted(EDGES))
def test_pmf_edges_match_oracle(edge, table):
    lv = uniform_levels(15) if table == "uniform" else _warped_levels(17)
    u = EDGES[edge](lv)
    np.testing.assert_allclose(np.asarray(expected_index_pmf(u, lv)),
                               _oracle_pmf(u, lv), atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested calls and branches included."""
    for eq in jaxpr.eqns:
        yield eq
        for v in eq.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("axis", [False, True], ids=["whole", "split"])
def test_aligned_leaf_is_never_copied(axis):
    """The program of the pass holds no concatenate, copy or reshape as
    large as the aligned leaf (it is read where it lies), while the
    misaligned leaves are still packed by one concatenate."""
    from jax.sharding import Mesh, PartitionSpec as P

    quant = QuantConfig(num_levels=15, bucket_size=B)
    ex = make_exchange(ExchangeConfig(compressor="qgenx", quant=quant))
    state = ex.init_state()
    tree = {"embed": jnp.ones((64, 2 * B)), "k_norm": jnp.ones((1, 128)),
            "w": jnp.ones((1, 40, 3 * B), jnp.bfloat16)}
    packed = -(-(128 + 40 * 3 * B) // B) * B  # k_norm, w and the tail
    if axis:
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        fn = jax.shard_map(
            lambda t, s: ex.coded_bits_tree(t, s, axis_name="data"),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    else:
        fn = ex.coded_bits_tree
    eqns = list(_eqns(jax.make_jaxpr(fn)(tree, state).jaxpr))
    moved = [math.prod(v.aval.shape) for eq in eqns
             if eq.primitive.name in ("concatenate", "copy", "copy_p",
                                      "reshape")
             for v in eq.outvars]
    assert max(moved) < tree["embed"].size, moved
    assert packed in [math.prod(v.aval.shape) for eq in eqns
                      if eq.primitive.name == "concatenate"
                      for v in eq.outvars]
