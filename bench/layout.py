"""The program's parameter tree, leaf by leaf.

The benchmark makes every weight itself, from the seed, with the
reference's generator; the architecture's module
(``bench/reference/<arch>.py``: ``program_params``, ``PROGRAM_KINDS``)
places it where the program keeps it and names the reference weights each
program leaf holds.  This module holds what every architecture shares:
norms by kind of weight, and the check that the benchmark's tree is the
program's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def path_of(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def kind_norms(tree, kinds: dict):
    """{reference kind: norm} of a program-layout tree (float32 math);
    ``kinds`` maps each leaf's path to its kind."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out[kinds[path_of(path)]] = jnp.linalg.norm(
            leaf.astype(jnp.float32).reshape(-1))
    return out


def check_same_tree(made, want) -> None:
    """Raise unless ``made`` has the structure, shapes and dtypes of the
    program's own init (``jax.eval_shape`` of it)."""
    a = {path_of(p): (tuple(l.shape), str(l.dtype))
         for p, l in jax.tree_util.tree_leaves_with_path(made)}
    b = {path_of(p): (tuple(l.shape), str(l.dtype))
         for p, l in jax.tree_util.tree_leaves_with_path(want)}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise RuntimeError(f"benchmark weights do not match the program's "
                           f"parameter tree: {diff[:6]}")
