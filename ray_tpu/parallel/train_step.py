"""Sharded train-step builder: the pjit data plane of the Train library.

Replaces the reference's DDP/FSDP wrapping (``train_loop_utils.py:263``
``prepare_model``) with the XLA-native formulation: params/optimizer state sharded by
spec trees, batch sharded over (dp, fsdp, sp), gradients reduced by the compiler over
ICI.  One jitted function = forward + backward + optimizer update, with donated state
(no double-buffered params in HBM).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import sharding as shard_rules
from ..models import transformer
from ..models.config import TransformerConfig
from ..models.transformer import ParallelContext
from ..util.profiler import PROGRAM_TRAIN_STEP, named_jit
from .mesh import named_sharding


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray


def make_optimizer(learning_rate: float = 3e-4, weight_decay: float = 0.1,
                   warmup_steps: int = 100, total_steps: int = 10_000,
                   b1: float = 0.9, b2: float = 0.95,
                   grad_clip: float = 1.0) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def state_shardings(cfg: TransformerConfig, mesh: Mesh,
                    optimizer: optax.GradientTransformation,
                    example_state_shapes) -> TrainState:
    """Build the NamedSharding tree for a TrainState: opt state leaves inherit
    the sharding of the param they track (ZeRO — optimizer sharded like params)."""
    pspecs = shard_rules.logical_param_specs(cfg)
    param_sh = named_sharding(mesh, pspecs)

    # optax states (adam mu/nu, etc.) embed subtrees with the exact param tree
    # structure — recurse and substitute the param sharding wherever a subtree
    # matches it; everything else (counts, scalars) is replicated.
    params_struct = jax.tree.structure(param_sh)

    def shard_opt_state(opt_shapes):
        def rec(node):
            try:
                if jax.tree.structure(node) == params_struct:
                    return param_sh
            except Exception:
                pass
            if hasattr(node, "_fields"):  # namedtuple (optax state classes)
                return type(node)(*(rec(x) for x in node))
            if isinstance(node, tuple):
                return tuple(rec(x) for x in node)
            if isinstance(node, list):
                return [rec(x) for x in node]
            if dataclasses.is_dataclass(node) and not isinstance(node, type):
                return type(node)(**{f.name: rec(getattr(node, f.name))
                                     for f in dataclasses.fields(node)})
            if isinstance(node, dict):
                return {k: rec(v) for k, v in node.items()}
            return NamedSharding(mesh, P())  # scalars: replicated
        return rec(opt_shapes)

    return TrainState(params=param_sh,
                      opt_state=shard_opt_state(example_state_shapes.opt_state),
                      step=NamedSharding(mesh, P()))


def init_sharded_state(cfg: TransformerConfig, mesh: Mesh,
                       optimizer: optax.GradientTransformation,
                       seed: int = 0, param_dtype=jnp.float32) -> Tuple[TrainState, TrainState]:
    """Initialize TrainState directly sharded on the mesh (out_shardings on the
    jitted init — params never materialize replicated)."""
    def init_fn():
        params = transformer.init_params(jax.random.PRNGKey(seed), cfg,
                                         dtype=param_dtype)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init_fn)
    shardings = state_shardings(cfg, mesh, optimizer, shapes)
    state = jax.jit(init_fn, out_shardings=shardings)()
    return state, shardings


def make_train_step(cfg: TransformerConfig, mesh: Mesh,
                    optimizer: optax.GradientTransformation,
                    state_sh: TrainState,
                    compute_dtype=jnp.bfloat16,
                    sp_axis: Optional[str] = None,
                    remat: Union[bool, str, None] = True, *,
                    grad_quant_enabled: bool = False,
                    quant_block: Optional[int] = None,
                    quant_stochastic: bool = False,
                    zero_sharded_update: bool = False,
                    opt_spec=None) -> Callable:
    """Returns jitted (state, batch) -> (state, metrics).

    With ``grad_quant_enabled`` and/or ``zero_sharded_update`` the step is
    built by ``zero.make_dp_train_step`` instead: an explicit dp-manual
    reduce-scatter / update / all-gather schedule with optional int8
    block-scaled wire payloads (see parallel/zero.py).  Both knobs off —
    the default — is byte-for-byte today's path.
    """
    transformer.refuse_layer_pattern(cfg, "make_train_step")
    shard_rules.refuse_mesh(cfg, mesh, "make_train_step")
    if grad_quant_enabled or zero_sharded_update:
        if shard_rules.expert_parallel(cfg):
            raise NotImplementedError(
                "grad_quant_enabled / zero_sharded_update build a dp-manual "
                "schedule (parallel/zero.py) that knows no ep axis: experts "
                "exchanged over ep train through the default step")
        from . import zero
        return zero.make_dp_train_step(
            cfg, mesh, optimizer, state_sh, compute_dtype=compute_dtype,
            sp_axis=sp_axis, remat=remat, grad_quant=grad_quant_enabled,
            quant_block=quant_block or zero.DEFAULT_BLOCK,
            quant_stochastic=quant_stochastic,
            zero_update=zero_sharded_update, opt_spec=opt_spec)
    pctx = ParallelContext(mesh=mesh, sp_axis=sp_axis,
                           batch_axes=shard_rules.batch_axes(cfg))
    batch_sh = named_sharding(mesh, shard_rules.batch_spec(cfg))

    loss_fn = functools.partial(transformer.causal_lm_loss, cfg=cfg, pctx=pctx,
                                compute_dtype=compute_dtype, remat=remat)

    def step_fn(state: TrainState, batch: Dict[str, jnp.ndarray]):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.params)
            new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["total_loss"] = loss
        return TrainState(new_params, new_opt, state.step + 1), metrics

    jitted = named_jit(
        PROGRAM_TRAIN_STEP, step_fn,
        in_shardings=(state_sh, None),  # batch sharding from the arrays
        out_shardings=(state_sh, None),
        donate_argnums=(0,))

    # Multi-controller (jax.distributed across hosts): each process feeds its
    # LOCAL slice of the global batch; device_put can't target non-addressable
    # shards (reference seam: train/torch/config.py rendezvous — here the
    # equivalent is the global-array assembly step).
    multiprocess = len({d.process_index for d in mesh.devices.flat}) > 1

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        import numpy as np
        if multiprocess:
            batch = {k: jax.make_array_from_process_local_data(
                batch_sh, np.asarray(v)) for k, v in batch.items()}
        else:
            batch = {k: jax.device_put(v, batch_sh) for k, v in batch.items()}
        return jitted(state, batch)

    step._jitted = jitted
    step.batch_sharding = batch_sh
    # wire/HBM accounting for the observability plane: the compiler-placed
    # fp32 gradient all-reduce over dp, and fully-replicated Adam state.
    # A ring all-reduce moves ~2x the payload per device (reduce-scatter
    # phase + all-gather phase) — counted as such so the number is
    # comparable with the explicit RS/AG schedule of parallel/zero.py.
    dp = 1
    for ax in shard_rules.batch_axes(cfg):
        dp *= mesh.shape.get(ax, 1)
    n_params = cfg.num_params()      # what this holder has of the model
    step.collective_bytes = (
        {("all_reduce", "float32"): 2 * n_params * 4} if dp > 1 else {})
    step.opt_state_bytes = 2 * n_params * 4 + 8
    return step


def make_eval_step(cfg: TransformerConfig, mesh: Mesh, state_sh: TrainState,
                   compute_dtype=jnp.bfloat16, sp_axis: Optional[str] = None):
    pctx = ParallelContext(mesh=mesh, sp_axis=sp_axis,
                           batch_axes=shard_rules.batch_axes(cfg))
    batch_sh = named_sharding(mesh, shard_rules.batch_spec(cfg))

    def eval_fn(params, batch):
        loss, metrics = transformer.causal_lm_loss(params, batch, cfg=cfg,
                                                   pctx=pctx,
                                                   compute_dtype=compute_dtype)
        return metrics

    return jax.jit(eval_fn, in_shardings=(state_sh.params, {"tokens": batch_sh}),
                   out_shardings=None)
