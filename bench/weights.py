"""Seeded weights of a dense decoder, made by the benchmark on the device.

The tree has the program's parameter layout (the harness checks it against
``model.abstract_params()``), but every value is drawn here, from the seed:
the program under test and the plain reference are both handed these
weights, and neither makes its own.

Layout rules stated in each configuration's ``layout``: query heads are
padded to a multiple of ``q_head_pad_to`` (the padded rows of the output
projection are zero, so padded heads add nothing), and the vocabulary to a
multiple of ``vocab_pad_to``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def pad_to(n: int, m: int) -> int:
    return int(math.ceil(n / m) * m)


def dims(cfg: dict) -> dict:
    """The sizes the weights and the reference need, from a config file."""
    lay = cfg["layout"]
    return {
        "d": cfg["hidden_size"], "f": cfg["intermediate_size"],
        "heads": cfg["num_attention_heads"],
        "heads_padded": pad_to(cfg["num_attention_heads"], lay["q_head_pad_to"]),
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
        "vocab_padded": pad_to(cfg["vocab_size"], lay["vocab_pad_to"]),
        "tied": cfg["tie_word_embeddings"], "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"],
    }


def leaf_specs(cfg: dict) -> list[tuple[tuple, tuple, str, float]]:
    """``(path, shape, kind, std)`` of every leaf, in a fixed order.
    ``kind``: "normal", "ones", or "wo" (normal with the padded-head rows
    zero)."""
    m = dims(cfg)
    d, f, hd = m["d"], m["f"], m["hd"]
    hq, hkv, vp = m["heads_padded"], m["kv_heads"], m["vocab_padded"]
    out = [(("embed", "table"), (vp, d), "normal", 0.02)]
    for i in range(m["layers"]):
        b = ("blocks", i)
        out += [
            (b + ("ln1", "scale"), (d,), "ones", 0.0),
            (b + ("ln2", "scale"), (d,), "ones", 0.0),
            (b + ("attn", "wq", "w"), (d, hq * hd), "normal", d ** -0.5),
            (b + ("attn", "wk", "w"), (d, hkv * hd), "normal", d ** -0.5),
            (b + ("attn", "wv", "w"), (d, hkv * hd), "normal", d ** -0.5),
            (b + ("attn", "wo", "w"), (hq * hd, d), "wo", (m["heads"] * hd) ** -0.5),
            (b + ("mlp", "w_gate", "w"), (d, f), "normal", d ** -0.5),
            (b + ("mlp", "w_up", "w"), (d, f), "normal", d ** -0.5),
            (b + ("mlp", "w_down", "w"), (f, d), "normal", f ** -0.5),
        ]
    out.append((("final_norm", "scale"), (d,), "ones", 0.0))
    if not m["tied"]:
        out.append((("lm_head", "w"), (d, vp), "normal", d ** -0.5))
    return out


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (seeds may exceed 32 signed
    bits)."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def _leaf(words, index: int, shape, kind: str, std: float, live_rows: int):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(words[0]), words[1]), index)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    x = jax.random.normal(key, shape, jnp.float32) * std
    if kind == "wo":
        x = jnp.where(jnp.arange(shape[0])[:, None] < live_rows, x, 0.0)
    return x


def to_tree(cfg: dict, leaves: list) -> dict:
    """Nest a flat list of leaves (in ``leaf_specs`` order) into the
    program's tree: ``{"embed": {...}, "blocks": [...], ...}``."""
    m = dims(cfg)
    tree: dict = {"blocks": [dict() for _ in range(m["layers"])]}
    for (path, _, _, _), x in zip(leaf_specs(cfg), leaves, strict=True):
        if path[0] == "blocks":
            node = tree["blocks"][path[1]]
            rest = path[2:]
        else:
            node = tree
            rest = path
        for k in rest[:-1]:
            node = node.setdefault(k, {})
        node[rest[-1]] = x
    return tree


def make_fn(cfg: dict):
    """``fn(words) -> params``: the whole tree in one traced call."""
    specs = leaf_specs(cfg)
    live = dims(cfg)["heads"] * cfg["head_dim"]

    def fn(words):
        return to_tree(cfg, [_leaf(words, i, shape, kind, std, live)
                             for i, (_, shape, kind, std) in enumerate(specs)])
    return fn


def make_params(cfg: dict, seed: int, sharding=None) -> dict:
    """The weights of ``seed``, fp32, made on the device in one jitted call
    (replicated over ``sharding``'s devices when given)."""
    fn = jax.jit(make_fn(cfg), out_shardings=sharding)
    return fn(jnp.asarray(seed_words(seed)))


def check_layout(cfg: dict, abstract_params) -> None:
    """Raise unless the program's parameter tree has exactly this layout."""
    want = jax.eval_shape(make_fn(cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
    got_s = jax.tree.structure(abstract_params)
    want_s = jax.tree.structure(want)
    if got_s != want_s:
        raise ValueError(f"program parameter tree {got_s} differs from the "
                         f"benchmark's layout {want_s}")
    for a, b in zip(jax.tree.leaves(abstract_params), jax.tree.leaves(want)):
        if tuple(a.shape) != tuple(b.shape):
            raise ValueError(f"program leaf {a.shape} vs layout {b.shape}")
