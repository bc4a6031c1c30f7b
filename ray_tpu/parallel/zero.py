"""ZeRO-sharded weight update + quantized gradient reduction: the dp-manual
train step.

The default train step (``train_step.make_train_step``) lets the XLA SPMD
partitioner place one fp32 all-reduce for the gradients and keeps the full
fp32 Adam state replicated on every data-parallel rank.  At >=1B params
that replication is what caps model size: Adam mu+nu alone is 8 bytes/param
per rank.  This module implements the two knobs that change it, per
"Automatic Cross-Replica Sharding of Weight Update" (ZeRO) and EQuARX
(PAPERS.md):

* ``zero_sharded_update`` — decompose the all-reduce into
  reduce-scatter -> local shard update -> all-gather(params): each rank
  owns 1/dp of the flattened parameter vector, keeps ONLY that shard's
  optimizer state (HBM ~ world_size x smaller), applies AdamW to the shard,
  and all-gathers the updated params.  AdamW is elementwise, so the shard
  update equals the replicated update restricted to the shard — the CPU
  exactness gate pins params allclose to the replicated path over 10 steps.
  The one cross-element op, global-norm clipping, is recovered exactly with
  a psum of per-shard square sums (same semantics as
  ``optax.clip_by_global_norm``).

* ``grad_quant_enabled`` — the reduce-scatter / all-gather payloads go
  int8 block-scaled over the wire (``quant_collectives``), ~4x fewer
  gradient bytes where DCN/ICI bandwidth bounds the dp step.

Both knobs build one full-manual shard_map over the whole step body:
full-manual is what makes the collective schedule explicit instead of
compiler-chosen.  The step
requires every mesh axis except dp (and a size-1 fsdp) to be trivial —
these knobs target the data-parallel axis, compose with tp/pp elsewhere
is future work.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer
from ..models.config import TransformerConfig
from ..models.transformer import ParallelContext
from ..util.profiler import PROGRAM_TRAIN_STEP, named_jit
from .quant_collectives import (DEFAULT_BLOCK, quantized_all_gather,
                                quantized_psum_scatter)
from .train_step import TrainState

__all__ = ["OptimizerSpec", "init_zero_state", "make_dp_train_step",
           "zero_opt_state_bytes"]


@dataclasses.dataclass(frozen=True)
class OptimizerSpec:
    """The hyperparameters behind ``train_step.make_optimizer``, reified.

    The ZeRO path applies the optimizer to a per-rank parameter shard, so
    it needs the raw hyperparameters (a built optax chain can't be split
    into its clip and AdamW stages after the fact).  ``build()`` returns
    exactly what ``make_optimizer`` with the same arguments returns.
    """
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0

    def schedule(self):
        return optax.warmup_cosine_decay_schedule(
            0.0, self.learning_rate, self.warmup_steps,
            max(self.total_steps, self.warmup_steps + 1))

    def adamw(self) -> optax.GradientTransformation:
        """The elementwise stage (everything but the global-norm clip)."""
        return optax.adamw(self.schedule(), b1=self.b1, b2=self.b2,
                           weight_decay=self.weight_decay)

    def build(self) -> optax.GradientTransformation:
        return optax.chain(optax.clip_by_global_norm(self.grad_clip),
                           self.adamw())


def _param_count(cfg: TransformerConfig, param_dtype) -> int:
    shapes = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg,
                                        dtype=param_dtype))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


def _padded(n: int, dp: int, block: int) -> int:
    """Flat length padded so both the dp split and the quant blocks tile."""
    unit = dp * block
    return -(-n // unit) * unit


def _validate_mesh(mesh: Mesh) -> int:
    dp = mesh.shape.get("dp", 1)
    extra = {a: s for a, s in mesh.shape.items() if a != "dp" and s > 1}
    if extra:
        raise ValueError(
            "grad_quant/zero_sharded_update shard over the dp axis only; "
            f"mesh has non-trivial axes {extra}")
    return dp


def zero_opt_state_bytes(cfg: TransformerConfig, mesh: Mesh,
                         quant_block: int = DEFAULT_BLOCK,
                         param_dtype=jnp.float32) -> int:
    """Per-rank resident optimizer-state bytes under the ZeRO split
    (Adam mu+nu fp32 shards + counters)."""
    dp = mesh.shape.get("dp", 1)
    npad = _padded(_param_count(cfg, param_dtype), dp, quant_block)
    return 2 * (npad // dp) * 4 + 8


def init_zero_state(cfg: TransformerConfig, mesh: Mesh,
                    opt_spec: Optional[OptimizerSpec] = None, *,
                    quant_block: int = DEFAULT_BLOCK, seed: int = 0,
                    param_dtype=jnp.float32) -> Tuple[TrainState, TrainState]:
    """TrainState for the ZeRO step: params replicated, optimizer state a
    flat fp32 vector [npad] sharded P("dp") — each rank materializes only
    its own mu/nu shard (out_shardings on the jitted init).

    The flat vector is the ravel of the param tree (ravel_pytree order),
    zero-padded so dp * quant_block tiles it; mu = nu = 0 and count = 0
    match ``optimizer.init`` of the replicated path exactly.
    """
    opt_spec = opt_spec or OptimizerSpec()
    dp = _validate_mesh(mesh)
    npad = _padded(_param_count(cfg, param_dtype), dp, quant_block)
    inner = opt_spec.adamw()

    def init_fn():
        params = transformer.init_params(jax.random.PRNGKey(seed), cfg,
                                         dtype=param_dtype)
        opt_state = inner.init({"p": jnp.zeros((npad,), jnp.float32)})
        return TrainState(params=params, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init_fn)
    shardings = TrainState(
        params=jax.tree.map(lambda _: NamedSharding(mesh, P()),
                            shapes.params),
        opt_state=jax.tree.map(
            lambda l: NamedSharding(mesh, P("dp") if l.ndim else P()),
            shapes.opt_state),
        step=NamedSharding(mesh, P()))
    state = jax.jit(init_fn, out_shardings=shardings)()
    return state, shardings


def collective_bytes_per_step(cfg: TransformerConfig, mesh: Mesh, *,
                              grad_quant: bool, zero_update: bool,
                              quant_block: int = DEFAULT_BLOCK,
                              param_dtype=jnp.float32) -> Dict[Tuple[str, str], int]:
    """Per-device wire bytes each step puts on the dp axis, by (op, dtype).

    The observability plane (StepTracker.set_collectives) turns this into
    ``raytpu_train_collective_bytes_total{op,dtype}``; it is also how the
    quant win is *visible*: flipping grad_quant moves the reduce bytes
    from float32 to int8 + a small float32 scale stream.
    """
    dp = mesh.shape.get("dp", 1)
    if dp <= 1:
        return {}
    npad = _padded(_param_count(cfg, param_dtype), dp, quant_block)
    out: Dict[Tuple[str, str], int] = {}

    def add(op, dtype, nbytes):
        out[(op, dtype)] = out.get((op, dtype), 0) + nbytes

    if grad_quant:  # grads: int8 payload + fp32 scale stream
        add("reduce_scatter", "int8", npad)
        add("reduce_scatter", "float32", npad // quant_block * 4)
    else:
        add("reduce_scatter", "float32", npad * 4)
    if zero_update:
        # updated params all-gather fp32 — weights stay lossless everywhere
        add("all_gather", "float32", npad * 4)
    elif grad_quant:
        add("all_gather", "int8", npad)
        add("all_gather", "float32", npad // quant_block * 4)
    else:
        add("all_gather", "float32", npad * 4)
    return out


def make_dp_train_step(cfg: TransformerConfig, mesh: Mesh,
                       optimizer: Optional[optax.GradientTransformation],
                       state_sh: TrainState,
                       compute_dtype=jnp.bfloat16,
                       sp_axis: Optional[str] = None,
                       remat: Union[bool, str, None] = True, *,
                       grad_quant: bool = False,
                       quant_block: int = DEFAULT_BLOCK,
                       quant_stochastic: bool = False,
                       zero_update: bool = False,
                       opt_spec: Optional[OptimizerSpec] = None,
                       param_dtype=jnp.float32) -> Callable:
    """The dp-manual (state, batch) -> (state, metrics) step.

    Drop-in for ``make_train_step`` when grad_quant and/or zero_update is
    on.  ``optimizer`` drives the update for the non-ZeRO arm (state from
    ``init_sharded_state``); the ZeRO arm uses ``opt_spec`` (state from
    ``init_zero_state``) because the update applies to a flat shard.
    """
    if sp_axis is not None and mesh.shape.get(sp_axis, 1) > 1:
        raise ValueError("sequence parallelism doesn't compose with the "
                         "dp-manual step; use the default train step")
    dp = _validate_mesh(mesh)
    if zero_update:
        opt_spec = opt_spec or OptimizerSpec()
    elif optimizer is None:
        raise ValueError("grad_quant without zero_update updates with the "
                         "stock optimizer; pass it")
    n = _param_count(cfg, param_dtype)
    npad = _padded(n, dp, quant_block)
    shard_len = npad // dp

    # inside the manual region everything is per-device local
    pctx = ParallelContext(manual_collectives=True)
    loss_fn = functools.partial(transformer.causal_lm_loss, cfg=cfg,
                                pctx=pctx, compute_dtype=compute_dtype,
                                remat=remat)

    def body(state: TrainState, batch: Dict[str, jnp.ndarray]):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch)
        flat_g, unravel = ravel_pytree(grads)
        flat_g = jnp.pad(flat_g.astype(jnp.float32), (0, npad - n))
        rank = jax.lax.axis_index("dp")
        if quant_stochastic:
            base = jax.random.fold_in(jax.random.PRNGKey(0x0E0A), state.step)
            rkey = jax.random.fold_in(base, rank)
            key_rs, key_ag = jax.random.split(rkey)
        else:
            key_rs = key_ag = None
        # local grads are local-batch means; sum/dp = global-batch mean
        if grad_quant:
            g_shard = quantized_psum_scatter(
                flat_g, "dp", dp, block=quant_block,
                stochastic=quant_stochastic, key=key_rs) / dp
        else:
            g_shard = jax.lax.psum_scatter(flat_g, "dp",
                                           scatter_dimension=0,
                                           tiled=True) / dp
        gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(g_shard * g_shard), "dp"))

        if zero_update:
            flat_p, unravel_p = ravel_pytree(state.params)
            flat_p = jnp.pad(flat_p.astype(jnp.float32), (0, npad - n))
            p_shard = jax.lax.dynamic_slice_in_dim(
                flat_p, rank * shard_len, shard_len)
            # optax.clip_by_global_norm, shard-wise: same select, psum'd norm
            g_shard = jax.lax.select(
                gnorm < opt_spec.grad_clip, g_shard,
                (g_shard / gnorm) * opt_spec.grad_clip)
            with jax.named_scope("optimizer"):
                updates, new_opt = opt_spec.adamw().update(
                    {"p": g_shard}, state.opt_state, {"p": p_shard})
                new_p_shard = optax.apply_updates({"p": p_shard},
                                                  updates)["p"]
            new_flat = jax.lax.all_gather(new_p_shard, "dp", tiled=True)
            new_params = unravel_p(new_flat[:n].astype(flat_p.dtype))
        else:
            if grad_quant:
                flat_mean = quantized_all_gather(
                    g_shard, "dp", block=quant_block,
                    stochastic=quant_stochastic, key=key_ag)
            else:
                flat_mean = jax.lax.all_gather(g_shard, "dp", tiled=True)
            grads_mean = unravel(flat_mean[:n])
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(
                    grads_mean, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)

        metrics = dict(metrics)
        metrics["total_loss"] = loss
        metrics = {k: (jax.lax.psum(v, "dp") if k == "tokens"
                       else jax.lax.pmean(v, "dp"))
                   for k, v in metrics.items()}
        metrics["grad_norm"] = gnorm
        return TrainState(new_params, new_opt, state.step + 1), metrics

    is_sh = lambda x: isinstance(x, NamedSharding)
    state_specs = jax.tree.map(lambda s: s.spec, state_sh, is_leaf=is_sh)
    batch_spec = P(tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names),
                   None)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, batch_spec),
        out_specs=(state_specs, P()),
        check_vma=False)
    jitted = named_jit(PROGRAM_TRAIN_STEP, sharded,
                       in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None), donate_argnums=(0,))

    batch_sh = NamedSharding(mesh, batch_spec)
    multiprocess = len({d.process_index for d in mesh.devices.flat}) > 1

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        if multiprocess:
            batch = {k: jax.make_array_from_process_local_data(
                batch_sh, np.asarray(v)) for k, v in batch.items()}
        else:
            batch = {k: jax.device_put(v, batch_sh) for k, v in batch.items()}
        return jitted(state, batch)

    step._jitted = jitted
    step.batch_sharding = batch_sh
    step.collective_bytes = collective_bytes_per_step(
        cfg, mesh, grad_quant=grad_quant, zero_update=zero_update,
        quant_block=quant_block, param_dtype=param_dtype)
    step.opt_state_bytes = (
        zero_opt_state_bytes(cfg, mesh, quant_block, param_dtype)
        if zero_update else 2 * n * 4 + 8)
    return step
