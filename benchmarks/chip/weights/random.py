"""Weight generator ``random``: every parameter of both models drawn from the
seed, on the device, in one jitted call, in bfloat16.

The tree is laid out as the serving program reads it (layers stacked on a
leading axis; attention weights (d, heads, head_dim); pages of the KV pool
are the program's own).  The harness checks the layout against the
program's before it hands the weights over.  Every parameter is drawn, the
biases and norm scales too, so a path that drops one of them shows in the
comparison with the reference."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A 64-bit seed as a threefry key: the same seed, the same key."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def _shapes(s: dict) -> dict:
    L, d, H, KH, hd, f, V = (s["layers"], s["d_model"], s["heads"],
                             s["kv_heads"], s["head_dim"], s["d_ff"],
                             s["vocab"])
    attn = {"wq": (L, d, H, hd), "wk": (L, d, KH, hd), "wv": (L, d, KH, hd),
            "wo": (L, H, hd, d)}
    if s["qkv_bias"]:
        attn.update(bq=(L, H, hd), bk=(L, KH, hd), bv=(L, KH, hd))
    if s["qk_norm"]:
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    tree = {
        "embed": (V, d),
        "layers": {"attn": attn, "ln1": {"scale": (L, d)},
                   "ln2": {"scale": (L, d)},
                   "mlp": {"wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)}},
        "final_norm": {"scale": (d,)},
    }
    if not s["tie"]:
        tree["lm_head"] = (V, d)
    return tree


def _scale(path: str, shape, s: dict):
    """(mean, std) of one parameter: fan-in scaling for projections, 0.02
    for embeddings and biases, 1 +- 0.1 for norm scales."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "q_norm", "k_norm"):
        return 1.0, 0.1
    if leaf in ("embed", "lm_head", "bq", "bk", "bv"):
        return 0.0, 0.02
    fan_in = {"wq": s["d_model"], "wk": s["d_model"], "wv": s["d_model"],
              "wo": s["heads"] * s["head_dim"], "wg": s["d_model"],
              "wu": s["d_model"], "wd": s["d_ff"]}[leaf]
    return 0.0, fan_in ** -0.5


def _draw(key, s: dict):
    shapes = _shapes(s)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        mean, std = _scale(name, shape, s)
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
