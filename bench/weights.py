"""Seeded weights of a decoder-only transformer, made from the
configuration file alone: the same seed gives the same weights to the
program (through an adapter to its layout) and to the plain reference.

Layout, with L layers stacked on the leading axis:

    embed (V, D); final_norm (D,)
    layers: ln1, ln2 (L, D)
            attn: wq (L, D, H, hd), wk and wv (L, D, KV, hd), wo (L, H, hd, D)
            mlp:  wg and wu (L, D, F), wd (L, F, D)
"""
from __future__ import annotations

import math

import numpy as np


def dims(model: dict) -> dict:
    D, H = model["hidden_size"], model["num_attention_heads"]
    return {"L": model["num_hidden_layers"], "D": D, "H": H,
            "KV": model["num_key_value_heads"], "hd": D // H,
            "F": model["intermediate_size"], "V": model["vocab_size"]}


def shapes(model: dict) -> dict:
    d = dims(model)
    L, D, H, KV, hd, F, V = (d[k] for k in ("L", "D", "H", "KV", "hd", "F",
                                            "V"))
    return {"embed": (V, D), "final_norm": (D,),
            "layers": {"ln1": (L, D), "ln2": (L, D),
                       "attn": {"wq": (L, D, H, hd), "wk": (L, D, KV, hd),
                                "wv": (L, D, KV, hd), "wo": (L, H, hd, D)},
                       "mlp": {"wg": (L, D, F), "wu": (L, D, F),
                               "wd": (L, F, D)}}}


def scale(name: str, model: dict) -> float | None:
    """The std a leaf is drawn with; ``None`` for norms, which start at 1."""
    d = dims(model)
    if name in ("ln1", "ln2", "final_norm"):
        return None
    if name == "embed":
        return 0.5 / math.sqrt(d["D"])
    if name == "wo":
        return 1.0 / math.sqrt(d["H"] * d["hd"])
    if name == "wd":
        return 1.0 / math.sqrt(d["F"])
    return 1.0 / math.sqrt(d["D"])          # wq wk wv wg wu


def seed_key(seed: int):
    """A threefry key from any whole-number seed (it may exceed 32 bits)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make(model: dict, key, dtype="float32") -> dict:
    """The weights (call inside ``jax.jit``; ``key`` from :func:`seed_key`)."""
    import jax
    import jax.numpy as jnp

    tree = shapes(model)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(paths):
        std = scale(path[-1].key, model)
        if std is None:
            leaves.append(jnp.ones(shape, dtype))
        else:
            leaves.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                            dtype) * jnp.asarray(std, dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)
