"""The plain reference against the program's own forward pass at a small
size on the CPU, with the benchmark's weights placed in the program's
tree by the architecture's module: the same losses in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import seeds
from bench.reference import qwen3

QWEN3 = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
         "departures": {"embedding_scaled_by_sqrt_hidden_size": True}}
def _program_loss(model_cfg, params, tokens, labels):
    from repro.launch.steps import make_loss_fn
    from repro.models.model import build

    return float(make_loss_fn(build(model_cfg))(
        params, {"tokens": tokens, "labels": labels}))


def _batch(vocab, seq):
    rng = np.random.default_rng(0)
    t = rng.integers(0, vocab, (2, seq + 1)).astype(np.int32)
    return jnp.asarray(t[:, :-1]), jnp.asarray(t[:, 1:])


def test_qwen3_reference_matches_the_program_in_float32():
    from repro.configs.base import ModelConfig

    dm = qwen3.Dims.from_config(QWEN3)
    root = seeds.root_key(2 ** 40 + 1)
    cfg = ModelConfig(name="q", arch_type="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, qk_norm=True,
                      rope_theta=1e6, d_ff=128, vocab_size=256,
                      dtype="float32")
    params = qwen3.program_params(qwen3.init_stacked(root, dm), dm,
                                  jnp.float32)
    t, l = _batch(256, 64)
    with jax.default_matmul_precision("highest"):
        want = float(qwen3.loss(qwen3.init_params(root, dm), t, l, dm))
        got = _program_loss(cfg, params, t, l)
    assert got == pytest.approx(want, rel=1e-4)
