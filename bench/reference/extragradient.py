"""Plain extragradient Adam, the optimizer every training reference
follows: the program's ``extra_adam`` with global-norm clipping, written
out in float32 with the exact mean over the chips' row blocks.

This module imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _train_fns(init, loss_fn, leaf_norms, opt: tuple):
    """Jitted pieces of the reference step, built so that one chip holds
    the parameters, both moments, one gradient and one block's work: the
    gradient accumulates in place, and only the committed step returns
    moments."""
    lr, b1, b2, eps, clip = opt
    tmap = jax.tree_util.tree_map
    zeros = jax.jit(lambda p: tmap(jnp.zeros_like, p))

    def acc_grad(acc, p, t, l):
        v, g = jax.value_and_grad(lambda q: loss_fn(q, t, l))(p)
        return tmap(jnp.add, acc, g), v

    def clip_scale(g):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        return jnp.minimum(1.0, clip / (gn + 1e-9))

    def moments(mu, nu, g):
        s = clip_scale(g)
        mu = tmap(lambda m, x: b1 * m + (1 - b1) * s * x, mu, g)
        nu = tmap(lambda v, x: b2 * v + (1 - b2) * (s * x) ** 2, nu, g)
        return mu, nu

    def apply(p, mu, nu, count):
        c = count.astype(jnp.float32)
        bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
        return tmap(lambda q, m, v: q - lr * (m / bc1) / (jnp.sqrt(v / bc2)
                                                          + eps), p, mu, nu)

    def lookahead(p, mu, nu, g, count):
        mu, nu = moments(mu, nu, g)
        return apply(p, mu, nu, count)

    def commit(p, mu, nu, g, count):
        mu, nu = moments(mu, nu, g)
        return apply(p, mu, nu, count), mu, nu

    def first_norms(g):
        s = clip_scale(g)
        return ({k: s * v for k, v in leaf_norms(g).items()}, leaf_norms(g))

    change = jax.jit(lambda a, b: leaf_norms(tmap(jnp.subtract, a, b)))
    scale = jax.jit(lambda g, s: tmap(lambda x: x * s, g), donate_argnums=0)
    return (zeros, jax.jit(acc_grad, donate_argnums=0), scale,
            jax.jit(lookahead), jax.jit(commit, donate_argnums=(0, 1, 2)),
            jax.jit(first_norms), change)


def extragradient_reference(init, loss_fn, leaf_norms, root, batches,
                            n_chips: int, opt: dict, steps: int = 3,
                            fault: str = ""):
    """``steps`` steps of extragradient Adam on the model whose float32
    parameters ``init(root)`` makes and whose mean loss over a block of
    rows is ``loss_fn(params, tokens, labels)``: the program's ``extra_adam``
    (gradient at the iterate, clipped Adam lookahead without committing the
    moments, gradient at the lookahead, clipped, committed) on the exact
    mean over ``n_chips`` equal row blocks of each global batch.

    ``batches``: list of (tokens, labels) int32 [B, S] global batches.
    ``fault`` plants one fault of the timed path, for reading its numbers:
    ``half_batch`` (each chip's mean over the first half of its rows),
    ``no_exchange`` (chip 0's own gradient, no mean across chips),
    ``unchanged`` (the step returns the state it was given).

    Returns per-step losses (at the lookahead, as the program reports
    them), the norm of each kind of the first committed gradient (after
    clipping, as the optimizer takes it), the norm of each kind of the
    parameters' change after ``steps`` steps, and the norms of the first
    raw gradient (for the leaf-exclusion rule).
    """
    key = tuple(float(opt[k]) for k in ("lr", "b1", "b2", "eps", "grad_clip"))
    with jax.default_matmul_precision("highest"):
        (zeros, acc_grad, scale, lookahead, commit, first_norms,
         change) = _train_fns(init, loss_fn, leaf_norms, key)

        def mean_grad(p, tokens, labels):
            blocks = np.split(np.arange(tokens.shape[0]), n_chips)
            if fault == "half_batch":
                blocks = [b[: len(b) // 2] for b in blocks]
            elif fault == "no_exchange":
                blocks = blocks[:1]
            g, total = zeros(p), 0.0
            for b in blocks:
                g, v = acc_grad(g, p, tokens[b], labels[b])
                total += float(v)
            n = len(blocks)
            return total / n, scale(g, jnp.float32(1.0 / n))

        p = init(root)
        mu, nu = zeros(p), zeros(p)
        losses, first_grad, raw_grad = [], None, None
        for t in range(steps):
            tokens, labels = batches[t]
            count = jnp.int32(t + 1)
            _, g1 = mean_grad(p, tokens, labels)
            half = lookahead(p, mu, nu, g1, count)
            del g1
            l2, g2 = mean_grad(half, tokens, labels)
            del half
            losses.append(l2)
            if t == 0:
                clipped, raw = first_norms(g2)
                first_grad = {k: float(v) for k, v in clipped.items()}
                raw_grad = {k: float(v) for k, v in raw.items()}
            if fault != "unchanged":
                p, mu, nu = commit(p, mu, nu, g2, count)
            del g2
        del mu, nu
        moved = {k: float(v) for k, v in change(p, init(root)).items()}
    return {"loss": losses, "grad_norms": first_grad,
            "update_norms": moved, "raw_grad_norms": raw_grad}
