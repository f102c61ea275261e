"""The plain reference computes the published model: the weights come in
the program's padded layout (query heads to a multiple of 16, vocabulary
to one of 128), and nothing the padding adds changes its logits, its loss
or its gradients."""

import jax
import jax.numpy as jnp
import numpy as np

from tiny_cells import TINY, parts

from bench import weights  # noqa: E402
from bench.reference import dense_decoder as ref  # noqa: E402


def _cfg(workload, **over):
    _, cfg, _, _, _, _ = parts(workload)
    return dict(cfg, **TINY, **over)


def _scramble_padding(params, m):
    """Fresh values in every padded column of ``wq``, row of ``wo`` and row
    of the vocabulary; the published part is left as it is."""
    live, vocab = m["heads"] * m["hd"], m["vocab"]
    key = jax.random.key(99)
    out = jax.tree.map(lambda x: x, params)
    for lp in out["blocks"]:
        a = lp["attn"]
        for name, axis in (("wq", 1), ("wo", 0)):
            w = a[name]["w"]
            noise = jax.random.normal(key, w.shape)
            keep = (jnp.arange(w.shape[axis]) < live)
            keep = keep[None, :] if axis == 1 else keep[:, None]
            a[name]["w"] = jnp.where(keep, w, noise)
    t = out["embed"]["table"]
    out["embed"]["table"] = jnp.where(jnp.arange(t.shape[0])[:, None] < vocab,
                                      t, 5.0)
    if "lm_head" in out:
        w = out["lm_head"]["w"]
        out["lm_head"]["w"] = jnp.where(jnp.arange(w.shape[1])[None, :] < vocab,
                                        w, 5.0)
    return out


def test_stream_logits_ignore_the_padding():
    cfg = _cfg("phi3-medium-14b.serve.decode_heavy", num_key_value_heads=2)
    m = weights.dims(cfg)
    assert m["heads_padded"] > m["heads"] and m["vocab_padded"] > m["vocab"]
    p = weights.make_params(cfg, 3)
    toks = np.arange(24, dtype=np.int32) * 17 % m["vocab"]
    a = np.asarray(ref.stream_logits(p, toks, m))
    b = np.asarray(ref.stream_logits(_scramble_padding(p, m), toks, m))
    assert a.shape == (24, m["vocab"])
    np.testing.assert_array_equal(a, b)


def test_training_ignores_the_padding():
    cfg = _cfg("minicpm-2b.train.1chip", num_key_value_heads=4)
    m = weights.dims(cfg)
    assert m["heads_padded"] > m["heads"] and m["vocab_padded"] > m["vocab"]
    opt = parts("minicpm-2b.train.1chip")[2]["optim"]
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, m["vocab"], (2, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, m["vocab"], (2, 32)), jnp.int32)
    p = weights.make_params(cfg, 3)
    a = ref.TrainReference(p, m, opt).step(toks, labels)
    p = _scramble_padding(weights.make_params(cfg, 3), m)
    b = ref.TrainReference(p, m, opt).step(toks, labels)
    assert a["loss"] == b["loss"]
    np.testing.assert_array_equal(a["leaf_grad_norms"], b["leaf_grad_norms"])
