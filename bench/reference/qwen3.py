"""Plain float32 reference of a Qwen3 dense decoder (hf ``Qwen3ForCausalLM``).

Straightforward ``jax.numpy`` with no kernels, no cache and no batching
tricks, following the published architecture: RMSNorm (eps from the
config), grouped-query attention with per-head RMSNorm on queries and
keys before rotary embeddings (rotate-half, ``rope_theta``), causal
softmax at ``1/sqrt(head_dim)``, SwiGLU MLP ``down(silu(gate(x)) *
up(x))``, final RMSNorm and logits tied to the embedding.

One departure, taken from the program and listed in PERF.md: when the
configuration says ``embedding_scaled_by_sqrt_hidden_size`` the input
embedding is multiplied by ``sqrt(hidden_size)`` (Qwen3 itself does not).

The weights are made here from the run's seed (``bench.seeds``), layer by
layer, so the reference never takes what the program made.  Every matmul
runs at ``precision="highest"`` (full float32 on a TPU).

Besides the reference, the module gives what the harness needs of this
architecture, found through the configuration's ``reference`` key
(:func:`bench.reference.of`): the operation and parameter counts
(``forward_flops``, ``param_count``) and the placing of these weights in
the program's parameter tree (``program_params``, ``PROGRAM_KINDS``).

This module imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench import seeds

LAYER_KINDS = ("ln1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "ln2",
               "gate", "up", "down")
GLOBAL_KINDS = ("embed", "ln_f")


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    theta: float
    eps: float
    embed_scale: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        d = c["hidden_size"]
        scaled = c.get("departures", {}).get(
            "embedding_scaled_by_sqrt_hidden_size", False)
        return cls(d=d, layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], ff=c["intermediate_size"],
                   vocab=c["vocab_size"], theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   embed_scale=math.sqrt(d) if scaled else 1.0)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def init_layer(root, dm: Dims, layer):
    """Layer ``layer``'s weights (float32, reference layout) from the root
    key; ``layer`` may be traced.  Normal at 1/sqrt(fan-in)."""
    k = seeds.layer_key(root, layer)
    ks = [jax.random.fold_in(k, i) for i in range(len(LAYER_KINDS))]
    D, H, KV, hd, F = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.ff
    return {
        "ln1": jnp.ones((D,), jnp.float32),
        "wq": _normal(ks[1], (D, H * hd), D ** -0.5),
        "wk": _normal(ks[2], (D, KV * hd), D ** -0.5),
        "wv": _normal(ks[3], (D, KV * hd), D ** -0.5),
        "q_norm": jnp.ones((hd,), jnp.float32),
        "k_norm": jnp.ones((hd,), jnp.float32),
        "wo": _normal(ks[6], (H * hd, D), (H * hd) ** -0.5),
        "ln2": jnp.ones((D,), jnp.float32),
        "gate": _normal(ks[8], (D, F), D ** -0.5),
        "up": _normal(ks[9], (D, F), D ** -0.5),
        "down": _normal(ks[10], (F, D), F ** -0.5),
    }


def init_globals(root, dm: Dims):
    k = seeds.layer_key(root, seeds.GLOBAL_LAYER)
    return {
        "embed": _normal(jax.random.fold_in(k, 0), (dm.vocab, dm.d),
                         dm.d ** -0.5),
        "ln_f": jnp.ones((dm.d,), jnp.float32),
    }


def init_params(root, dm: Dims):
    """Whole model: {"embed", "ln_f", "layers": [per-layer dicts]}."""
    p = init_globals(root, dm)
    p["layers"] = [init_layer(root, dm, l) for l in range(dm.layers)]
    return p


def init_stacked(root, dm: Dims):
    """Whole model with each kind of layer weight stacked over layers:
    {"embed", "ln_f", "layers": {kind: [layers, ...]}}."""
    p = init_globals(root, dm)
    p["layers"] = jax.vmap(lambda l: init_layer(root, dm, l))(
        jnp.arange(dm.layers))
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, precision="highest")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """Rotate-half rotary embedding; x [B, S, H, hd], positions [S]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def layer_forward(p, x, dm: Dims):
    """One decoder layer on x [B, S, D] (float32)."""
    B, S, _ = x.shape
    H, KV, hd = dm.heads, dm.kv_heads, dm.head_dim
    pos = jnp.arange(S)
    h = rms_norm(x, p["ln1"], dm.eps)
    q = _mm(h, p["wq"], "bsd,de->bse").reshape(B, S, H, hd)
    k = _mm(h, p["wk"], "bsd,de->bse").reshape(B, S, KV, hd)
    v = _mm(h, p["wv"], "bsd,de->bse").reshape(B, S, KV, hd)
    q = rope(rms_norm(q, p["q_norm"], dm.eps), pos, dm.theta)
    k = rope(rms_norm(k, p["k_norm"], dm.eps), pos, dm.theta)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = _mm(q, k, "bqhd,bkhd->bhqk") / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = _mm(jax.nn.softmax(s, axis=-1), v, "bhqk,bkhd->bqhd")
    x = x + _mm(a.reshape(B, S, H * hd), p["wo"], "bse,ed->bsd")
    h = rms_norm(x, p["ln2"], dm.eps)
    g = _mm(h, p["gate"], "bsd,df->bsf")
    u = _mm(h, p["up"], "bsd,df->bsf")
    return x + _mm(jax.nn.silu(g) * u, p["down"], "bsf,fd->bsd")


def embed(g, tokens, dm: Dims):
    return g["embed"][tokens] * dm.embed_scale


def logits_of(g, h, dm: Dims):
    """Final norm and tied output projection; h [..., D]."""
    return _mm(rms_norm(h, g["ln_f"], dm.eps), g["embed"], "...d,vd->...v")


def loss(params, tokens, labels, dm: Dims):
    """Mean next-token cross-entropy over every position."""
    x = embed(params, tokens, dm)
    for p in params["layers"]:
        x = layer_forward(p, x, dm)
    lg = logits_of(params, x, dm)
    lse = jax.nn.logsumexp(lg, axis=-1)
    pick = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - pick)


# ---------------------------------------------------------------------------
# training: three steps of extragradient Adam
# ---------------------------------------------------------------------------


def leaf_norms(params):
    """Norm of each kind of weight, stacked over layers."""
    out = {k: jnp.linalg.norm(params[k]) for k in GLOBAL_KINDS}
    for kind in LAYER_KINDS:
        out[kind] = jnp.sqrt(sum(jnp.sum(jnp.square(p[kind]))
                                 for p in params["layers"]))
    return out


@functools.lru_cache(maxsize=None)
def _fns(dm: Dims):
    return (jax.jit(lambda r: init_params(r, dm)),
            lambda p, t, l: loss(p, t, l, dm))


def train_reference(cfg: dict, root, batches, n_chips: int, opt: dict,
                    steps: int = 3, fault: str = ""):
    """Extragradient Adam on this model from the seed
    (:func:`bench.reference.extragradient.extragradient_reference`)."""
    from bench.reference.extragradient import extragradient_reference

    init, loss_fn = _fns(Dims.from_config(cfg))
    return extragradient_reference(init, loss_fn, leaf_norms, root, batches,
                                   n_chips, opt, steps, fault)


# ---------------------------------------------------------------------------
# counts (bench/flops.py's conventions)
# ---------------------------------------------------------------------------


def matmul_params(c: dict) -> int:
    """Matmul weights, with the tied output projection (the logits)
    counted once."""
    D, L = c["hidden_size"], c["num_hidden_layers"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    F, V = c["intermediate_size"], c["vocab_size"]
    per_layer = D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F
    return L * per_layer + V * D


def param_count(c: dict) -> int:
    """Every weight: matmuls, the embedding (tied) and the norms."""
    D, L, hd = c["hidden_size"], c["num_hidden_layers"], c["head_dim"]
    return matmul_params(c) + L * (2 * D + 2 * hd) + D


def forward_flops(c: dict, tokens: int, context: int) -> float:
    """Forward operations of ``tokens`` tokens, each attending to
    ``context`` positions: 2 per matmul weight and 4 x layers x heads x
    head_dim x context for the scores and values."""
    attn = (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)
    return tokens * (2.0 * matmul_params(c) + attn)


# ---------------------------------------------------------------------------
# these weights in the program's parameter tree
# ---------------------------------------------------------------------------


def program_params(ref, dm: Dims, dtype):
    """Weights ``ref`` (float32, layers stacked: :func:`init_stacked`) in
    the layout of the program's dense decoder of period 1: layers stacked
    along a leading axis, attention weights as [D, heads, head_dim] /
    [heads, head_dim, D], matmul weights in the model's dtype, norms and
    the embedding in float32."""
    L, D, H, KV, hd = dm.layers, dm.d, dm.heads, dm.kv_heads, dm.head_dim
    ly = ref["layers"]
    w = lambda kind: ly[kind].astype(dtype)  # noqa: E731
    layer = {
        "ln_attn": {"scale": ly["ln1"]},
        "attn": {
            "wq": w("wq").reshape(L, D, H, hd),
            "wk": w("wk").reshape(L, D, KV, hd),
            "wv": w("wv").reshape(L, D, KV, hd),
            "wo": w("wo").reshape(L, H, hd, D),
            "q_norm": ly["q_norm"],
            "k_norm": ly["k_norm"],
        },
        "ln_mlp": {"scale": ly["ln2"]},
        "mlp": {"wi": w("up"), "wg": w("gate"), "wo": w("down")},
    }
    return {"embed": ref["embed"], "layers": (layer,), "layers_tail": (),
            "ln_f": {"scale": ref["ln_f"]}}


#: program leaf path -> the kind of reference weight it holds
PROGRAM_KINDS = {
    "embed": "embed", "ln_f/scale": "ln_f",
    "layers/0/ln_attn/scale": "ln1", "layers/0/attn/wq": "wq",
    "layers/0/attn/wk": "wk", "layers/0/attn/wv": "wv",
    "layers/0/attn/wo": "wo", "layers/0/attn/q_norm": "q_norm",
    "layers/0/attn/k_norm": "k_norm", "layers/0/ln_mlp/scale": "ln2",
    "layers/0/mlp/wi": "up", "layers/0/mlp/wg": "gate",
    "layers/0/mlp/wo": "down",
}
