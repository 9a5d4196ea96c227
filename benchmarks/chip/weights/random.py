"""Weight generator ``random``: every parameter of both models drawn from the
seed, on the device, in one jitted call, in bfloat16.

The tree and each leaf's mean and spread are the spec's architecture
module's (``param_shapes``, ``param_scale``), laid out as the serving
program reads them; the harness checks the layout against the program's
before it hands the weights over.  Every parameter is drawn, the
biases and norm scales too, so a path that drops one of them shows in the
comparison with the reference."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.models import arch


def seed_key(seed: int):
    """A 64-bit seed as a threefry key: the same seed, the same key."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def leaf_plan(s: dict):
    """[(path, shape, draw index)] of the spec's parameter tree (its
    architecture module's ``param_shapes``), in the tree's leaf order; the
    index is folded into the key to draw that leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        arch(s).param_shapes(s), is_leaf=lambda x: isinstance(x, tuple))
    return [("/".join(str(getattr(p, "key", p)) for p in path), shape, i)
            for i, (path, shape) in enumerate(flat)]


def _draw(key, s: dict):
    mod = arch(s)
    treedef = jax.tree.structure(mod.param_shapes(s),
                                 is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for name, shape, i in leaf_plan(s):
        mean, std = mod.param_scale(name, s)
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaves.append((mean + std * x).astype(jnp.bfloat16))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def generate(target: dict, draft: dict, seed: int):
    """(target params, draft params) of the two specs from ``seed``."""
    fn = _generator(tuple(sorted(target.items())),
                    tuple(sorted(draft.items())))
    return fn(seed_key(seed))


@functools.lru_cache(maxsize=4)
def _generator(target_items, draft_items):
    t, d = dict(target_items), dict(draft_items)

    @jax.jit
    def gen(key):
        kt, kd = jax.random.split(key)
        return _draw(kt, t), _draw(kd, d)
    return gen
