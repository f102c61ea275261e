"""Serving-step builders: prefill and single-token decode over the manual
mesh.  Decode state is donated so caches update in place.

``weight_mode``:
* ``resident`` — params live model-sharded (replicated over data); right for
  archs whose bf16 weights fit 16 GB / model_size.
* ``gathered`` — params stored as FSDP flat shards over (pod, data) and
  ring-all-gathered per layer at use (the only way a 400B model serves on a
  (16, 16) mesh; the roofline shows the cost honestly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from repro.models.model_api import Model
from repro.runtime.train_step import (FsdpPlan, TrainStepConfig, _flat_spec,
                                      make_ctx, _slice_to_local)
from repro.sharding import rules as shard_rules


def _require_decoder_only(cfg, what: str) -> None:
    """Gathered serving streams params through ``transformer.forward`` /
    ``decode_step``, which only model decoder-only transformer stacks.  Any
    other family (encdec cross-attention, ssm / hybrid recurrent state,
    audio frontends) would silently produce garbage, so refuse at build
    time — not at trace time, and not just for encdec."""
    if cfg.family not in ("dense", "moe") or cfg.frontend is not None:
        raise NotImplementedError(
            f"gathered {what} is decoder-only: family={cfg.family!r} "
            f"frontend={cfg.frontend!r} is not supported (use "
            f"weight_mode='resident')")


def _batch_axis(mesh: Mesh, global_batch: int):
    bspec = shard_rules.batch_spec(global_batch, mesh)
    return tuple(bspec)[0] if len(bspec) else None


def _batch_specs(batch_abs, batch_axes):
    def one(path, leaf):
        return P(*((batch_axes,) + (None,) * (len(leaf.shape) - 1)))
    return jax.tree_util.tree_map_with_path(one, batch_abs)


def build_prefill(model: Model, mesh: Mesh, shape_cfg, *,
                  weight_mode: str = "resident", causal_skip: bool = True):
    """Returns (prefill_fn(params, batch) -> local-vocab logits, param_specs)."""
    ctx = make_ctx(mesh)
    batch_axes = _batch_axis(mesh, shape_cfg.global_batch)
    vocab_ax = "model" if "model" in mesh.axis_names else None
    specs_abs = model.input_specs(shape_cfg)
    bspecs = _batch_specs(specs_abs, batch_axes)

    if weight_mode == "gathered":
        _require_decoder_only(model.cfg, "prefill")
        plan = FsdpPlan(model, mesh, TrainStepConfig(dp_mode="fsdp"))
        pspecs = {"groups": {name: [_flat_spec(mesh)] * plan.plans[name].n_buckets
                             for name in plan.groups}}

        def fn(params, batch):
            tree, resolver = plan.params_and_resolver(params["groups"],
                                                      jnp.bfloat16)
            from repro.models import transformer

            logits, _, _ = transformer.forward(tree, batch["tokens"], model.cfg,
                                               ctx=ctx,
                                               extra_embeds=batch.get("extra_embeds"),
                                               causal_skip=causal_skip,
                                               block_resolver=resolver)
            return logits
    else:
        pspecs = model.param_specs(mesh)

        def fn(params, batch):
            return model.forward(params, batch, ctx=ctx,
                                 causal_skip=causal_skip)

    out_spec = P(batch_axes, None, vocab_ax)
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(pspecs, bspecs),
                            out_specs=out_spec, check_vma=False)
    return jax.jit(sharded), pspecs


def build_decode_step(model: Model, mesh: Mesh, shape_cfg, *,
                      weight_mode: str = "resident", donate: bool = True):
    """Returns (decode(params, token, state, pos) -> (logits, state),
    param_specs, state_specs)."""
    ctx = make_ctx(mesh)
    b, s = shape_cfg.global_batch, shape_cfg.seq_len
    state_abs = model.abstract_decode_state(b, s)
    state_specs = shard_rules.decode_state_specs(state_abs, model.cfg, mesh, b)
    batch_axes = _batch_axis(mesh, b)
    vocab_ax = "model" if "model" in mesh.axis_names else None

    if weight_mode == "gathered":
        _require_decoder_only(model.cfg, "decode")
        plan = FsdpPlan(model, mesh, TrainStepConfig(dp_mode="fsdp"))
        pspecs = {"groups": {name: [_flat_spec(mesh)] * plan.plans[name].n_buckets
                             for name in plan.groups}}

        def fn(params, token, state, pos):
            tree, resolver = plan.params_and_resolver(params["groups"],
                                                      jnp.bfloat16)
            return model.decode_step(tree, token, state, pos, ctx=ctx,
                                     seq_len=s, block_resolver=resolver)
    else:
        pspecs = model.param_specs(mesh)

        def fn(params, token, state, pos):
            return model.decode_step(params, token, state, pos, ctx=ctx,
                                     seq_len=s)

    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspecs, P(batch_axes), state_specs, P()),
        out_specs=(P(batch_axes, vocab_ax), state_specs),
        check_vma=False)
    step = jax.jit(sharded, donate_argnums=(2,) if donate else ())
    return step, pspecs, state_specs
