"""ParallelCtx: explicit model-parallel collectives for the manual step.

The whole train/serve step runs inside a *fully-manual* ``shard_map`` (every
mesh axis manual) — the design consequence of making the paper's reducer the
real DP reduction (GSPMD would otherwise insert its own).  Model code
therefore sees *local* weight shards and calls ``ctx.psum`` explicitly after
row-parallel contractions — Megatron-style TP, but with every collective
visible to our scheduler and to the roofline accounting.

``ParallelCtx()`` (no axes) is the single-device context: every collective
degrades to the identity, so the same model code runs in smoke tests.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class ParallelCtx:
    model_axis: str | None = None        # manual TP axis ("model")
    data_axes: tuple[str, ...] = ()      # manual DP axes (("pod","data"))
    # optional Communicator-backed all_to_all (ctx-level EP dispatch);
    # signature (x, *, split_axis, concat_axis).  None -> native
    # lax.all_to_all fallback in :meth:`all_to_all`.
    a2a: Any = field(default=None, compare=False)

    # -- model-axis collectives ------------------------------------------------

    def psum(self, x):
        """Row-parallel completion sum whose *output is replicated* across
        the model axis.  Under ``check_vma=False`` the raw ``lax.psum``
        transpose would re-psum the (replicated) cotangent and scale grads
        by the axis size — so this uses an identity-backward custom VJP
        (correct exactly because every consumer treats the output as
        replicated)."""
        return _psum_id_bwd(x, self.model_axis) if self.model_axis else x

    def fan_out(self, x):
        """Megatron's ``f``: identity forward on a replicated activation
        that is about to feed rank-sharded (column-parallel) branches;
        backward psums the per-rank varying cotangents so upstream
        cotangents are replicated again.  Dual of :meth:`psum` (``g``)."""
        return _psum_grad(x, self.model_axis) if self.model_axis else x

    def pmax(self, x):
        return lax.pmax(x, self.model_axis) if self.model_axis else x

    def model_size(self) -> int:
        return lax.axis_size(self.model_axis) if self.model_axis else 1

    def model_index(self):
        return lax.axis_index(self.model_axis) if self.model_axis else 0

    def all_to_all(self, x, *, split_axis: int, concat_axis: int):
        """Tiled all-to-all over the model axis (EP dispatch/combine).

        Routes through the attached :class:`repro.comm.Communicator`
        transport when one was wired in (``TrainStepConfig.moe_transport``),
        else the native ``lax.all_to_all``.  A tiled all-to-all is a pure
        permutation, so its autodiff transpose — the inverse all-to-all —
        is already correct under ``check_vma=False``; no custom VJP.
        """
        if self.model_axis is None:
            return x
        if self.a2a is not None:
            return self.a2a(x, split_axis=split_axis, concat_axis=concat_axis)
        return lax.all_to_all(x, self.model_axis, split_axis, concat_axis,
                              tiled=True)

    def gather_replicated(self, x):
        """All-gather a model-axis batch shard back to a replicated tensor
        (identity backward: the output is consumed as replicated, so each
        rank's true cotangent is just its own slice — the gather dual of
        :meth:`psum`)."""
        return _gather_id_bwd(x, self.model_axis) if self.model_axis else x

    # -- data-axis helpers -----------------------------------------------------

    def dp_world(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= lax.axis_size(a)
        return n

    def psum_data(self, x):
        for a in self.data_axes:
            x = lax.psum(x, a)
        return x

    def pmean_data(self, x):
        n = self.dp_world()
        return self.psum_data(x) / n if self.data_axes else x


SINGLE = ParallelCtx()


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_id_bwd(x, axis):
    return lax.psum(x, axis)


def _psum_id_fwd(x, axis):
    return lax.psum(x, axis), None


def _psum_id_bwd_rule(axis, _, ct):
    return (ct,)


_psum_id_bwd.defvjp(_psum_id_fwd, _psum_id_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_id_bwd(x, axis):
    return lax.all_gather(x, axis, tiled=True)


def _gather_id_fwd(x, axis):
    return lax.all_gather(x, axis, tiled=True), x.shape[0]


def _gather_id_bwd_rule(axis, n_local, ct):
    i = lax.axis_index(axis)
    return (lax.dynamic_slice_in_dim(ct, i * n_local, n_local, axis=0),)


_gather_id_bwd.defvjp(_gather_id_fwd, _gather_id_bwd_rule)


# ---------------------------------------------------------------------------
# gradient synchronisation for model-replicated weights with rank-dependent
# use (kv projections under the GQA head-gather): forward identity, backward
# psum over the model axis — each rank's partial cotangent sums to the true
# gradient.  Works identically under replicated/zero1/fsdp because the sum
# happens before the FSDP gather-transpose sees the cotangent.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_grad(x, axis):
    return x


def _psum_grad_fwd(x, axis):
    return x, None


def _psum_grad_bwd(axis, _, ct):
    return (lax.psum(ct, axis),)


_psum_grad.defvjp(_psum_grad_fwd, _psum_grad_bwd)


def sum_grads_over_model(tree, ctx: ParallelCtx):
    """Identity on values; cotangents are psum'd over the model axis."""
    if ctx.model_axis is None:
        return tree
    return jax.tree.map(lambda t: _psum_grad(t, ctx.model_axis), tree)
