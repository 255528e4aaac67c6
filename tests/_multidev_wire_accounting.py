"""Subprocess payload: wire-bytes accounting + int4 end-to-end exactness.

Run with 8 forced host devices.  For every (bits, mode) combination this
asserts two things about the flat qgenx exchange (``Exchange.pmean``):

1. **Honest wire bytes** — the byte-size of every buffer actually handed
   to a collective (recorded at trace time via ``wire_trace_start``)
   equals :func:`exchange_buffer_bytes`.  In 4-bit mode the gathered
   payload must be the *packed* buffer: ~n/2 bytes, not n.

2. **Bit-exact exchange** — the multi-device result equals a host-side
   re-implementation of the exchange built from the jnp reference kernels
   with the same per-device folded keys (<= 1e-6).

The Pallas kernel path is exercised single-device elsewhere
(tests/test_kernels.py, tests/test_dequant_reduce.py — bit-exact vs the
same jnp reference used here); inside an 8-fake-device shard_map on a
1-core CPU container the interpret-mode Python callbacks can starve the
collective rendezvous, so this script runs the jnp reference path.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools  # noqa: E402
import math  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from repro.core.exchange import (  # noqa: E402
    ExchangeConfig,
    exchange_buffer_bytes,
    make_exchange,
    wire_bytes_per_device,
    wire_trace_start,
    wire_trace_stop,
)
from repro.core.quantization import QuantConfig, uniform_levels, _pad_to_buckets  # noqa: E402
from repro.kernels.ref import dequantize_blocks_ref, quantize_blocks_ref  # noqa: E402

K = 8
N = 5000  # deliberately NOT a multiple of bucket * K — exercises padding
BUCKET = 256

assert jax.device_count() == K, jax.device_count()
mesh = Mesh(np.array(jax.devices()).reshape(K), ("data",))

xs = jnp.asarray(np.random.RandomState(0).randn(K, N), jnp.float32)


def run_exchange(cfg, levels, mode, key):
    ex = make_exchange(ExchangeConfig(
        compressor="qgenx", quant=cfg, axis_name="data", mode=mode,
        use_pallas=False,
    ))

    @functools.partial(jax.jit, static_argnames=())
    def run(x, k):
        def f(xl, kk):
            out, _ = ex.pmean(xl.reshape(-1), ex.init_state(), kk)
            return out.reshape(1, N)

        return shard_map(
            f, mesh=mesh, in_specs=(P("data", None), P()),
            out_specs=P("data", None), check_vma=False,
        )(x, k)

    return run(xs, key)


def ref_gather(cfg, levels, key):
    """mean_k DEQ(Q(x_k)) with the same folded keys as compressed_pmean."""
    q_is_inf = math.isinf(cfg.q_norm)
    outs = []
    for i in range(K):
        ki = jax.random.fold_in(key, i)
        k1, _ = jax.random.split(ki)
        x2d, _ = _pad_to_buckets(xs[i], cfg.bucket_size)
        noise = jax.random.uniform(k1, x2d.shape, dtype=jnp.float32)
        idx, norms = quantize_blocks_ref(
            x2d, noise, levels, q_is_inf=q_is_inf, bits=cfg.bits
        )
        deq = dequantize_blocks_ref(idx, norms, levels, bits=cfg.bits)
        outs.append(deq.reshape(-1))
    return jnp.mean(jnp.stack(outs), axis=0)[:N]


def ref_two_phase(cfg, levels, key):
    """Chunked quantize -> a2a -> mean -> requantize -> gather, host-side."""
    q_is_inf = math.isinf(cfg.q_norm)
    b = cfg.bucket_size
    quota = K * b
    n_pad = -(-N // quota) * quota
    chunk = n_pad // K
    nbpc = chunk // b
    # phase 1: every device quantizes its full (padded) vector
    idxs, normss, k2s = [], [], []
    for i in range(K):
        ki = jax.random.fold_in(key, i)
        k1, k2 = jax.random.split(ki)
        k2s.append(k2)
        x2d = jnp.pad(xs[i], (0, n_pad - N)).reshape(K * nbpc, b)
        noise = jax.random.uniform(k1, x2d.shape, dtype=jnp.float32)
        idx, norms = quantize_blocks_ref(
            x2d, noise, levels, q_is_inf=q_is_inf, bits=cfg.bits
        )
        idxs.append(idx.reshape(K, nbpc, -1))
        normss.append(norms.reshape(K, nbpc))
    # phase 2: device j reduces chunk j and re-quantizes it
    chunks = []
    for j in range(K):
        deq = jnp.stack([
            dequantize_blocks_ref(
                idxs[i][j], normss[i][j], levels, bits=cfg.bits
            ).reshape(-1)
            for i in range(K)
        ])
        reduced = jnp.mean(deq, axis=0)
        noise2 = jax.random.uniform(k2s[j], (nbpc, b), dtype=jnp.float32)
        ridx, rnorms = quantize_blocks_ref(
            reduced.reshape(nbpc, b), noise2, levels, q_is_inf=q_is_inf, bits=cfg.bits
        )
        chunks.append(
            dequantize_blocks_ref(ridx, rnorms, levels, bits=cfg.bits).reshape(-1)
        )
    return jnp.concatenate(chunks)[:N]


for bits, s in ((8, 15), (4, 5)):
    cfg = QuantConfig(num_levels=s, q_norm=math.inf, bucket_size=BUCKET, bits=bits)
    levels = uniform_levels(s)
    for mode in ("gather", "two_phase"):
        key = jax.random.PRNGKey(17 * bits + (mode == "gather"))
        wire_trace_start()
        out = np.asarray(run_exchange(cfg, levels, mode, key))
        rec = wire_trace_stop()
        assert np.allclose(out, out[0:1], atol=1e-6), f"{bits}/{mode} replicas differ"

        got = dict(rec)
        assert len(got) == len(rec), f"duplicate trace names: {rec}"
        want = exchange_buffer_bytes(N, K, cfg, mode)
        assert got == want, (bits, mode, got, want)
        # 4-bit: the payload crossing the wire is the PACKED buffer (~n/2)
        if bits == 4 and mode == "gather":
            nb = -(-N // BUCKET)
            assert got["gather_payload"] == nb * BUCKET // 2, got
        # analytic per-device transmit model must agree with the buffers too
        wb = wire_bytes_per_device(N, K, cfg, mode)
        if mode == "gather":
            assert wb == sum(want.values()), (wb, want)
        print(f"PASS accounting bits={bits} mode={mode} {got}", flush=True)

        ref = np.asarray(
            ref_gather(cfg, levels, key) if mode == "gather"
            else ref_two_phase(cfg, levels, key)
        )
        err = np.abs(out[0] - ref).max()
        assert err <= 1e-6, (bits, mode, err)
        print(f"PASS e2e-exact bits={bits} mode={mode} maxerr={err:.2e}", flush=True)

print("ALL OK", flush=True)
