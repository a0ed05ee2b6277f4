"""Train/serve step builders: pjit sharding, microbatch accumulation, and
the explicit-DP compressed-gradient variant.

``make_train_step`` is the production path: GSPMD shards params/optimizer
state per the model's spec tree; gradient reduction happens inside the
compiled program (overlapped with the backward pass by XLA's latency-hiding
scheduler — compute/comm overlap comes from the compiler, the framework's
job is to keep the collectives off the critical path, see §Perf).

``make_compressed_dp_step`` demonstrates int8 error-feedback gradient
compression over an explicit shard_map data-parallel axis (8x less gradient
traffic; used when ICI/DCN bandwidth — e.g. cross-pod — is the bottleneck).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from . import optimizer as opt

Array = jax.Array
Pytree = Any


def make_train_step(loss_fn: Callable[[Pytree, Any], Array],
                    opt_cfg: opt.AdamWConfig,
                    microbatches: int = 1,
                    cast_dtype: Optional[Any] = None):
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches`` > 1 splits the (already device-sharded) batch on axis 0
    and accumulates grads in fp32 via lax.scan — activation memory divides
    by the microbatch count while keeping the same global batch.

    ``cast_dtype`` (e.g. bf16) casts the floating param tree ONCE per step
    before the loss.  Without it, ``w.astype(x.dtype)`` inside the layer
    makes GSPMD all-gather the f32 master weights and convert *after* —
    2x the FSDP wire bytes and 2x the gathered-weight HBM traffic (§Perf
    hillclimb 2, iteration 1).  Grads flow back through the cast, arriving
    f32 for the optimizer; the dp reduction itself runs in cast_dtype.
    """
    if cast_dtype is not None:
        inner_loss = loss_fn

        def loss_fn(p, b):  # noqa: F811 — deliberate wrap
            pc = jax.tree.map(
                lambda x: x.astype(cast_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
            return inner_loss(pc, b)

    def step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                mb = b // microbatches
                return x[:mb * microbatches].reshape(
                    microbatches, mb, *x.shape[1:])
            mbatch = jax.tree.map(split, batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def acc(carry, mb):
                tot_l, tot_g = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                tot_g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), tot_g, g)
                return (tot_l + l, tot_g), None

            (loss, grads), _ = jax.lax.scan(
                acc, (jnp.zeros(()), zeros), mbatch)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        params, opt_state, info = opt.apply_update(
            params, grads, opt_state, opt_cfg)
        return params, opt_state, {"loss": loss, **info}

    return step


def jit_train_step(step_fn, mesh: Mesh, param_spec: Pytree,
                   batch_spec: Pytree, donate: bool = True):
    """Compile with explicit in/out shardings (params+opt state sharded per
    spec, batch per batch_spec, metrics replicated)."""
    def to_sharding(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))
    ps = to_sharding(param_spec)
    os_ = opt.AdamWState(step=NamedSharding(mesh, P()),
                         m=ps, v=ps)
    bs = to_sharding(batch_spec)
    rep = NamedSharding(mesh, P())
    return jax.jit(step_fn,
                   in_shardings=(ps, os_, bs),
                   out_shardings=(ps, os_, rep),
                   donate_argnums=(0, 1) if donate else ())


def make_compressed_dp_step(loss_fn, opt_cfg: opt.AdamWConfig, mesh: Mesh,
                            dp_axes=("pod", "data")):
    """Explicit-DP step: params replicated, batch sharded over dp_axes,
    gradients all-reduced with int8 error-feedback compression."""

    def local_step(params, opt_state, residual, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, residual = opt.compressed_psum(grads, residual, dp_axes)
        loss = jax.lax.pmean(loss, dp_axes)
        params, opt_state, info = opt.apply_update(
            params, grads, opt_state, opt_cfg)
        return params, opt_state, residual, {"loss": loss, **info}

    rep = P()
    shard0 = P(dp_axes)  # spec prefix: batch pytree sharded on axis 0
    mapped = shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, rep, rep, shard0),
        out_specs=(rep, rep, rep, rep), check_vma=False)
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def make_serve_step(apply_fn: Callable[..., Any]):
    """Wrap a pure forward for serving; jitted by the caller with the
    appropriate shardings (see launch/dryrun.py)."""
    @functools.wraps(apply_fn)
    def serve(params, *inputs):
        return apply_fn(params, *inputs)
    return serve
