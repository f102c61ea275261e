"""Seeded weights of a decoder with multi-head latent attention and expert
layers (DeepSeek-V3's layers), made by the benchmark on the device.

The tree has the program's parameter layout (``check_layout`` holds it to
``model.abstract_params()``), and every value is drawn here from the seed:
the program and the plain reference are both handed these weights.  The
leading ``first_k_dense_replace`` layers hold a dense MLP, the others an
expert layer of which this chip holds ``num_experts_held`` experts (from
``first_expert_held``), its router over all ``n_routed_experts`` and its
selection bias ``score_bias`` (drawn small and non-zero, so that selection
and weighting differ), and the shared experts as one GLU.  Every leaf is
stored at the configuration's parameter precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import seed_words

BIAS_STD = 0.02   # e_score_correction_bias: small next to the sigmoid scores


def dims(cfg: dict) -> dict:
    """The sizes the weights and the reference need, from a config file."""
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "lora": cfg["kv_lora_rank"],
        "dense_ff": cfg["intermediate_size"],
        "expert_ff": cfg["moe_intermediate_size"],
        "shared_ff": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "experts": cfg["n_routed_experts"], "held": cfg["num_experts_held"],
        "first": cfg["first_expert_held"], "top_k": cfg["num_experts_per_tok"],
        "scaling": cfg["routed_scaling_factor"],
        "dense_layers": cfg["first_k_dense_replace"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "tied": cfg["tie_word_embeddings"],
    }


def _glu(prefix, d, f):
    return [(prefix + ("w_gate", "w"), (d, f), "normal", d ** -0.5),
            (prefix + ("w_up", "w"), (d, f), "normal", d ** -0.5),
            (prefix + ("w_down", "w"), (f, d), "normal", f ** -0.5)]


def leaf_specs(cfg: dict) -> list[tuple[tuple, tuple, str, float]]:
    """``(path, shape, kind, std)`` of every leaf, in a fixed order;
    ``kind``: "normal" or "ones"."""
    m = dims(cfg)
    d, hh, r = m["d"], m["heads"], m["lora"]
    dn, dr, dv = m["nope"], m["rope"], m["v"]
    e, f = m["held"], m["expert_ff"]
    out = [(("embed", "table"), (m["vocab"], d), "normal", 0.02)]
    for i in range(m["layers"]):
        b = ("blocks", i)
        a = b + ("mla",)
        out += [
            (b + ("ln1", "scale"), (d,), "ones", 0.0),
            (b + ("ln2", "scale"), (d,), "ones", 0.0),
            (a + ("q_proj", "w"), (d, hh * (dn + dr)), "normal", d ** -0.5),
            (a + ("kv_a_proj", "w"), (d, r + dr), "normal", d ** -0.5),
            (a + ("kv_a_norm", "scale"), (r,), "ones", 0.0),
            (a + ("kv_b_proj", "w"), (r, hh * (dn + dv)), "normal", r ** -0.5),
            (a + ("o_proj", "w"), (hh * dv, d), "normal", (hh * dv) ** -0.5),
        ]
        if i < m["dense_layers"]:
            out += _glu(b + ("mlp",), d, m["dense_ff"])
            continue
        x = b + ("moe",)
        out += [
            (x + ("router", "w"), (d, m["experts"]), "normal", d ** -0.5),
            (x + ("score_bias",), (m["experts"],), "normal", BIAS_STD),
            (x + ("w_gate",), (e, d, f), "normal", d ** -0.5),
            (x + ("w_up",), (e, d, f), "normal", d ** -0.5),
            (x + ("w_down",), (e, f, d), "normal", f ** -0.5),
        ] + _glu(x + ("shared",), d, m["shared_ff"])
    out.append((("final_norm", "scale"), (d,), "ones", 0.0))
    if not m["tied"]:
        out.append((("lm_head", "w"), (d, m["vocab"]), "normal", d ** -0.5))
    return out


def _leaf(words, index: int, shape, kind: str, std: float, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(words[0]), words[1]), index)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _tree(cfg: dict, leaves: list) -> dict:
    tree: dict = {"blocks": [dict() for _ in range(cfg["num_hidden_layers"])]}
    for (path, _, _, _), x in zip(leaf_specs(cfg), leaves, strict=True):
        node, rest = ((tree["blocks"][path[1]], path[2:])
                      if path[0] == "blocks" else (tree, path))
        for k in rest[:-1]:
            node = node.setdefault(k, {})
        node[rest[-1]] = x
    return tree


def make_fn(cfg: dict):
    """``fn(words) -> params``: the whole tree in one traced call."""
    specs = leaf_specs(cfg)
    dtype = jnp.dtype(cfg["precision"]["params"])

    def fn(words):
        return _tree(cfg, [_leaf(words, i, shape, kind, std, dtype)
                           for i, (_, shape, kind, std) in enumerate(specs)])
    return fn


def make_params(cfg: dict, seed: int, sharding=None) -> dict:
    """The weights of ``seed``, made on the device in one jitted call."""
    fn = jax.jit(make_fn(cfg), out_shardings=sharding)
    return fn(jnp.asarray(seed_words(seed)))


def check_layout(cfg: dict, abstract_params) -> None:
    """Raise unless the program's parameter tree has exactly this layout:
    the same structure, shapes and dtypes."""
    want = jax.eval_shape(make_fn(cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
    got_s, want_s = (jax.tree.structure(abstract_params),
                     jax.tree.structure(want))
    if got_s != want_s:
        raise ValueError(f"program parameter tree {got_s} differs from the "
                         f"benchmark's layout {want_s}")
    for a, b in zip(jax.tree.leaves(abstract_params), jax.tree.leaves(want)):
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            raise ValueError(f"program leaf {a.shape} {a.dtype} vs layout "
                             f"{b.shape} {b.dtype}")
