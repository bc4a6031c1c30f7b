"""Pipeline parallelism: GPipe-style microbatching inside one jitted program.

The reference has no pipeline parallelism in core (SURVEY §2.3 PP row —
delegated to Alpa/DeepSpeed on top of Ray actors).  The TPU-native design runs
the whole pipeline *inside* a single SPMD program under ``shard_map``:

* the stacked layer params [L, ...] are reshaped to [P, L/P, ...] and the
  stage dim is sharded over the ``pp`` mesh axis — each device holds one
  stage's layers;
* microbatches march through stages on a ``lax.scan`` over
  ``M + P - 1`` ticks; each tick every stage runs its layers on its current
  microbatch, then activations rotate one hop along the ``pp`` ring with
  ``ppermute`` (ICI neighbor traffic, overlapping the next tick's compute);
* stage 0 injects embedded microbatches, the last stage's outputs are
  collected from the scan ys, and the loss (final norm + chunked CE) runs on
  the last stage only — ``where``-masked, SPMD-uniform;
* autodiff of the scan+ppermute gives the reverse pipeline schedule for
  gradients; the replicated in-specs of embed/head params transpose into the
  correct cross-stage psums.

Composes with ``dp`` (batch sharding) in the same shard_map.  Bubble fraction
is the GPipe (P-1)/(M+P-1); pick num_microbatches >= 4*P to amortize — or use
``virtual_stages`` V > 1 (``interleaved_pipeline_loss_fn``) for the
Megatron-style interleaved schedule, which cuts the fill bubble to
(P-1)/V stage-times at the cost of V× more (smaller) ppermute hops.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import sharding as shard_rules
from ..models import transformer
from ..models.config import TransformerConfig
from .mesh import named_sharding
from .train_step import TrainState


def partition_layers(params, num_stages: int, virtual_stages: int = 1):
    """Reshape every stacked-layer leaf [L, ...] -> [P, V*Lc, ...].

    With ``virtual_stages`` V > 1 the assignment is INTERLEAVED
    (Megatron-style): device d owns chunks d, P+d, 2P+d, … of the V*P
    total chunks, so layers [L] -> [V, P, Lc] -> transpose -> [P, V, Lc]
    -> flatten the local dims to [P, V*Lc].  A microbatch then makes V
    circuits of the ring, running one chunk per visit."""
    def fix(x):
        L = x.shape[0]
        assert L % (num_stages * virtual_stages) == 0, \
            (L, num_stages, virtual_stages)
        lc = L // (num_stages * virtual_stages)
        tail = x.shape[1:]
        if virtual_stages == 1:
            return x.reshape(num_stages, lc, *tail)
        x = x.reshape(virtual_stages, num_stages, lc, *tail)
        x = jnp.swapaxes(x, 0, 1)
        return x.reshape(num_stages, virtual_stages * lc, *tail)
    return {**params, "blocks": jax.tree.map(fix, params["blocks"])}


def merge_layers(params, virtual_stages: int = 1):
    """Inverse of partition_layers."""
    def fix(x):
        P_, VL = x.shape[0], x.shape[1]
        tail = x.shape[2:]
        if virtual_stages == 1:
            return x.reshape(P_ * VL, *tail)
        lc = VL // virtual_stages
        x = x.reshape(P_, virtual_stages, lc, *tail)
        x = jnp.swapaxes(x, 0, 1)
        return x.reshape(P_ * VL, *tail)
    return {**params, "blocks": jax.tree.map(fix, params["blocks"])}


def pipeline_param_specs(cfg: TransformerConfig,
                         auto_axes: Tuple[str, ...] = ()) -> Dict[str, Any]:
    """PartitionSpec tree for stage-partitioned params: blocks get a leading
    pp stage dim; embed/head/final-norm replicated across stages (their grads
    psum through the shard_map in-spec transpose).

    ``auto_axes`` retains those mesh axes from the logical (tensor-parallel)
    specs — used to build the STATE sharding when the pipeline shard_map
    leaves e.g. ``tp`` automatic (pp manual + tp compiler-inserted
    collectives).  With the default empty tuple this is the manual in-spec
    view: everything but pp/dp replicated."""
    base = shard_rules.logical_param_specs(cfg)

    def keep(d):
        return d if d in auto_axes else None

    def add_stage_dim(spec: P) -> P:
        # original leading dim was the layer dim (None).
        return P("pp", *[keep(d) for d in spec])

    blocks = jax.tree.map(add_stage_dim, base["blocks"],
                          is_leaf=lambda x: isinstance(x, P))

    def outer(spec: P) -> P:
        return P(*[keep(d) for d in spec])

    out = {k: (blocks if k == "blocks" else
               jax.tree.map(outer, v, is_leaf=lambda x: isinstance(x, P)))
           for k, v in base.items()}
    return out


def _stage_apply(x, stage_params, cfg, positions, compute_dtype,
                 pctx=transformer.ParallelContext()):
    """Run this device's L/P layers on x [mb, S, H]."""
    def body(x, layer_params):
        x, aux = transformer.block_forward(x, layer_params, cfg, positions,
                                           pctx)
        return x, aux

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, aux = jax.lax.scan(body, x, stage_params)
    return x, aux.sum()


def _pp_axis_split(mesh: Mesh, dp_axes, sp_axis: str):
    """Partition the mesh axes for the pipeline shard_map.

    Returns (dp_axes, sp, auto_axes): dp_axes are the MANUAL batch axes,
    ``sp`` is the manual sequence axis (or None), and auto_axes stay with
    the COMPILER — tp's megatron collectives and fsdp's ZeRO
    gather/reduce-scatter of the stage-sharded params are both inserted by
    XLA from the storage shardings (scaling-book recipe), so composing
    pp x fsdp needs no hand-written gathers."""
    auto_axes = tuple(a for a in ("tp", "fsdp") if a in mesh.axis_names
                      and mesh.shape[a] > 1)
    dp_axes = tuple(a for a in dp_axes if a in mesh.axis_names
                    and mesh.shape[a] > 1 and a not in auto_axes) or None
    sp = (sp_axis if sp_axis in mesh.axis_names
          and mesh.shape[sp_axis] > 1 else None)
    return dp_axes, sp, auto_axes


def _final_stage_loss(final, params, targets, cfg, loss_chunk,
                      p_idx, n_stages, dp_axes, pp_axis):
    """Loss head shared by both pipeline schedules: final-norm + lm-head +
    (chunked) CE on the LAST stage, psum-masked SPMD-uniform, pmean over
    data axes (batch AND, under sequence parallelism, the sp shard axis —
    every shard holds an equal token count, so mean-of-means is exact)."""
    n, s, h = final.shape[0] * final.shape[1], final.shape[2], final.shape[3]
    final = final.reshape(n, s, h)
    x = transformer._norm(final, params["final_norm"], cfg)
    w = transformer.lm_head_weight(params, cfg, x.dtype)
    tgt = targets.reshape(n, s)
    chunk = loss_chunk
    if chunk == 0:
        chunk = 512 if s * cfg.vocab_size > 2 ** 25 else None
    if chunk:
        nll = transformer.chunked_cross_entropy(x, w, tgt, min(chunk, s))
    else:
        logits = (x @ w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    local_loss = nll.mean()
    loss = jax.lax.psum(
        jnp.where(p_idx == n_stages - 1, local_loss, 0.0), pp_axis)
    if dp_axes:
        loss = jax.lax.pmean(loss, dp_axes)
    return loss


def _wrap_pipeline_loss(smapped):
    def loss_fn(params, batch):
        if "targets" in batch:
            tokens, targets = batch["tokens"], batch["targets"]
        else:
            tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        loss, moe_aux = smapped(params, tokens, targets)
        total = loss + 0.01 * moe_aux
        return total, {"loss": loss, "moe_aux_loss": moe_aux,
                       "tokens": tokens.size}
    return loss_fn


def pipeline_loss_fn(cfg: TransformerConfig, mesh: Mesh,
                     num_microbatches: int,
                     compute_dtype=jnp.bfloat16,
                     loss_chunk: Optional[int] = 0,
                     pp_axis: str = "pp",
                     dp_axes: Tuple[str, ...] = ("dp", "fsdp"),
                     sp_axis: str = "sp"):
    """Returns loss(params_staged, batch) -> (loss, metrics), shard_mapped
    over the pp (stages), dp (batch) and sp (sequence, ring attention) mesh
    axes; tp and fsdp stay automatic (compiler-inserted collectives — fsdp
    is the ZeRO sharding of the stage-local params and optimizer state)."""
    M = num_microbatches
    dp_axes, sp, auto_axes = _pp_axis_split(mesh, dp_axes, sp_axis)
    if sp and not cfg.use_rope:
        raise ValueError("pp x sp needs RoPE positions (learned positional "
                         "embeddings are not sequence-shard aware)")

    pspec_tree = pipeline_param_specs(cfg)
    batch_dim = dp_axes if dp_axes and len(dp_axes) > 1 else (
        dp_axes[0] if dp_axes else None)
    batch_spec = P(batch_dim, sp)
    reduce_axes = tuple(dp_axes or ()) + ((sp,) if sp else ()) or None
    pctx = transformer.ParallelContext(mesh=mesh, sp_axis=sp,
                                       manual_collectives=True)

    def body(params, tokens, targets):
        p_idx = jax.lax.axis_index(pp_axis)
        n_stages = jax.lax.psum(1, pp_axis)
        # Local view of the stage-sharded blocks has stage-dim extent 1.
        stage = jax.tree.map(lambda x: x[0], params["blocks"])
        b_local, s = tokens.shape   # s is the sp-LOCAL sequence shard
        mb = b_local // M
        positions = jnp.arange(s)
        if sp:
            positions = positions + jax.lax.axis_index(sp) * s

        toks_mb = tokens.reshape(M, mb, s)
        h = cfg.hidden_size

        def tick(carry, t):
            act = carry
            # Inject microbatch t at stage 0 (all ranks compute the cheap
            # embed; the where selects). Clamp t to a valid index for the
            # trailing bubble ticks.
            t_in = jnp.clip(t, 0, M - 1)
            inject = transformer.embed_tokens(
                params, jax.lax.dynamic_index_in_dim(toks_mb, t_in, 0,
                                                     keepdims=False),
                cfg, compute_dtype)
            act = jnp.where((p_idx == 0) & (t < M), inject, act)
            act, aux = _stage_apply(act, stage, cfg, positions,
                                    compute_dtype, pctx)
            # Rotate activations one hop forward along the pp ring; the wrap
            # from the last stage back to 0 carries garbage that the next
            # tick's stage-0 inject overwrites.
            nxt = jax.lax.ppermute(
                act, pp_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return nxt, (act, aux)

        init = jnp.zeros((mb, s, h), compute_dtype)
        _, (outs, auxes) = jax.lax.scan(tick, init, jnp.arange(M + n_stages - 1))

        # The last stage produced microbatch m's output at tick m + P - 1.
        # n_stages is static on a concrete mesh: mesh.shape[pp_axis].
        P_static = mesh.shape[pp_axis]
        final = outs[P_static - 1: P_static - 1 + M]        # [M, mb, S, H]
        loss = _final_stage_loss(final, params, targets, cfg, loss_chunk,
                                 p_idx, n_stages, reduce_axes, pp_axis)
        moe_aux = jax.lax.psum(auxes.sum(), pp_axis) / (M * n_stages)
        if reduce_axes:
            moe_aux = jax.lax.pmean(moe_aux, reduce_axes)
        return loss, moe_aux

    param_specs = jax.tree.map(lambda s: s, pspec_tree,
                               is_leaf=lambda x: isinstance(x, P))

    smap_kwargs: Dict[str, Any] = {}
    if auto_axes:
        manual = {pp_axis} | set(dp_axes or ()) | ({sp} if sp else set())
        smap_kwargs["axis_names"] = manual
    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, batch_spec, batch_spec),
        out_specs=(P(), P()),
        check_vma=False, **smap_kwargs)
    return _wrap_pipeline_loss(smapped)


def interleaved_pipeline_loss_fn(cfg: TransformerConfig, mesh: Mesh,
                                 num_microbatches: int, virtual_stages: int,
                                 compute_dtype=jnp.bfloat16,
                                 loss_chunk: Optional[int] = 0,
                                 pp_axis: str = "pp",
                                 dp_axes: Tuple[str, ...] = ("dp", "fsdp"),
                                 sp_axis: str = "sp"):
    """Interleaved (virtual-stage) pipeline schedule — Megatron-style.

    Device d owns V layer chunks (global chunks d, P+d, 2P+d, …); a
    microbatch makes V circuits of the pp ring, running ONE chunk per
    device visit, so each tick is 1/V of a GPipe stage-time and the
    pipeline-fill bubble shrinks from (P-1) stage-times to (P-1)/V.
    Microbatches inject in waves of P every V*P ticks (a ring slot frees
    exactly when its resident finishes circuit V); the schedule is fully
    static, so the whole thing stays one ``lax.scan`` inside ``shard_map``
    — autodiff gives the reverse interleaved schedule for free.

    Because the schedule is static, each resident's identity is a pure
    function of (device, tick): a resident injected at tick t0 has made
    h = t - t0 hops, sits on device h mod P, circuit h // P — so device d
    at tick t solves c = ((t - d) mod V*P) // P and
    m = ((t - h) div V*P)*P + ((t - h) mod V*P).  Only the activation
    itself rides the ppermute ring; chunk selection is a dynamic slice of
    the device's [V*Lc] local layer stack; embeddings are precomputed once
    outside the scan; finished outputs (c == V-1 at the last stage) write
    into a carried output buffer that the final-stage loss consumes."""
    M = num_microbatches
    V = virtual_stages
    dp_axes, sp, auto_axes = _pp_axis_split(mesh, dp_axes, sp_axis)
    if sp and not cfg.use_rope:
        raise ValueError("pp x sp needs RoPE positions (learned positional "
                         "embeddings are not sequence-shard aware)")
    P_static = mesh.shape[pp_axis]
    assert M % P_static == 0, \
        (f"interleaved schedule injects waves of P: num_microbatches {M} "
         f"must be a multiple of pp={P_static}")
    n_ticks = (M // P_static) * V * P_static + P_static - 1

    pspec_tree = pipeline_param_specs(cfg)
    batch_dim = dp_axes if dp_axes and len(dp_axes) > 1 else (
        dp_axes[0] if dp_axes else None)
    batch_spec = P(batch_dim, sp)
    reduce_axes = tuple(dp_axes or ()) + ((sp,) if sp else ()) or None
    pctx = transformer.ParallelContext(mesh=mesh, sp_axis=sp,
                                       manual_collectives=True)

    def body(params, tokens, targets):
        p_idx = jax.lax.axis_index(pp_axis)
        n_stages = jax.lax.psum(1, pp_axis)
        stage = jax.tree.map(lambda x: x[0], params["blocks"])  # [V*Lc,...]
        n_layers_local = jax.tree.leaves(stage)[0].shape[0]
        lc = n_layers_local // V
        b_local, s = tokens.shape
        mb = b_local // M
        positions = jnp.arange(s)
        if sp:
            positions = positions + jax.lax.axis_index(sp) * s
        h = cfg.hidden_size
        VP = V * n_stages
        # Embeddings once, outside the scan (the per-tick inject only
        # indexes this buffer).
        emb_mb = transformer.embed_tokens(params, tokens, cfg,
                                          compute_dtype).reshape(M, mb, s, h)

        def tick(carry, t):
            act, out_buf, aux_sum = carry
            # Resident identity is analytic in (p_idx, t) — see docstring.
            r = (t - p_idx) % VP
            c = r // n_stages                    # circuit of this resident
            t0 = t - (c * n_stages + p_idx)      # its injection tick
            m = (t0 // VP) * n_stages + t0 % VP  # its microbatch
            valid = (t0 >= 0) & (m < M)
            m_safe = jnp.clip(m, 0, M - 1)
            # stage 0, circuit 0: this tick IS the injection
            act = jnp.where((p_idx == 0) & (c == 0),
                            jax.lax.dynamic_index_in_dim(emb_mb, m_safe, 0,
                                                         keepdims=False),
                            act)
            # run this visit's chunk: rows [c*lc, (c+1)*lc) of the local
            # layer stack
            chunk = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, c * lc, lc, 0),
                stage)
            act, aux = _stage_apply(act, chunk, cfg, positions, compute_dtype,
                                    pctx)
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
            # a resident finishing circuit V-1 at the last stage is done
            done = (p_idx == n_stages - 1) & (c == V - 1) & valid
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf,
                jnp.where(done, act,
                          jax.lax.dynamic_index_in_dim(out_buf, m_safe, 0,
                                                       keepdims=False)),
                m_safe, 0)
            act = jax.lax.ppermute(
                act, pp_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (act, out_buf, aux_sum), ()

        init = (jnp.zeros((mb, s, h), compute_dtype),
                jnp.zeros((M, mb, s, h), compute_dtype),
                jnp.zeros((), jnp.float32))
        (_act, out_buf, aux_sum), _ = jax.lax.scan(
            tick, init, jnp.arange(n_ticks))

        loss = _final_stage_loss(out_buf, params, targets, cfg, loss_chunk,
                                 p_idx, n_stages, reduce_axes, pp_axis)
        # Same convention as the GPipe path (sum over all layer-chunk aux
        # values / (M * P)) so the two schedules are interchangeable.
        moe_aux = jax.lax.psum(aux_sum, pp_axis) / (M * P_static)
        if reduce_axes:
            moe_aux = jax.lax.pmean(moe_aux, reduce_axes)
        return loss, moe_aux

    smap_kwargs: Dict[str, Any] = {}
    if auto_axes:
        smap_kwargs["axis_names"] = ({pp_axis} | set(dp_axes or ())
                                     | ({sp} if sp else set()))
    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec_tree, batch_spec, batch_spec),
        out_specs=(P(), P()),
        check_vma=False, **smap_kwargs)
    return _wrap_pipeline_loss(smapped)


def init_pp_state(cfg: TransformerConfig, mesh: Mesh,
                  optimizer: optax.GradientTransformation, seed: int = 0,
                  param_dtype=jnp.float32,
                  virtual_stages: int = 1) -> Tuple[TrainState, TrainState]:
    """Initialize a stage-partitioned TrainState sharded over the mesh."""
    num_stages = mesh.shape["pp"]

    def init_fn():
        params = transformer.init_params(jax.random.PRNGKey(seed), cfg,
                                         dtype=param_dtype)
        params = partition_layers(params, num_stages, virtual_stages)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    # State arrays keep their tensor-parallel AND ZeRO (fsdp) shardings on
    # top of the stage partition — the loss shard_map treats both as
    # automatic axes, so XLA inserts the tp matmul collectives and the
    # fsdp param-gather / grad-reduce-scatter from these storage shardings.
    auto = tuple(a for a in ("tp", "fsdp") if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    pspecs = pipeline_param_specs(cfg, auto_axes=auto)
    param_sh = named_sharding(mesh, pspecs)
    shapes = jax.eval_shape(init_fn)
    from .train_step import state_shardings as _ss  # reuse opt-state recursion

    # state_shardings builds from logical_param_specs; do the same recursion
    # against the pipeline specs instead.
    params_struct = jax.tree.structure(param_sh)

    def shard_opt_state(node):
        try:
            if jax.tree.structure(node) == params_struct:
                return param_sh
        except Exception:
            pass
        if hasattr(node, "_fields"):
            return type(node)(*(shard_opt_state(x) for x in node))
        if isinstance(node, tuple):
            return tuple(shard_opt_state(x) for x in node)
        if isinstance(node, list):
            return [shard_opt_state(x) for x in node]
        if dataclasses.is_dataclass(node) and not isinstance(node, type):
            return type(node)(**{f.name: shard_opt_state(getattr(node, f.name))
                                 for f in dataclasses.fields(node)})
        if isinstance(node, dict):
            return {k: shard_opt_state(v) for k, v in node.items()}
        return NamedSharding(mesh, P())

    sh = TrainState(params=param_sh,
                    opt_state=shard_opt_state(shapes.opt_state),
                    step=NamedSharding(mesh, P()))
    state = jax.jit(init_fn, out_shardings=sh)()
    return state, sh


def make_pp_train_step(cfg: TransformerConfig, mesh: Mesh,
                       optimizer: optax.GradientTransformation,
                       state_sh: TrainState, num_microbatches: int = 4,
                       compute_dtype=jnp.bfloat16,
                       loss_chunk: Optional[int] = 0,
                       virtual_stages: int = 1) -> Callable:
    """Jitted pipeline train step over a mesh with a pp axis (+ optional
    dp).  ``virtual_stages`` > 1 selects the interleaved schedule (the
    state must be initialized with the same value)."""
    if virtual_stages > 1:
        loss_fn = interleaved_pipeline_loss_fn(
            cfg, mesh, num_microbatches, virtual_stages, compute_dtype,
            loss_chunk)
    else:
        loss_fn = pipeline_loss_fn(cfg, mesh, num_microbatches,
                                   compute_dtype, loss_chunk)
    batch_sh = NamedSharding(mesh, shard_rules.batch_spec())

    def step_fn(state: TrainState, batch):
        (total, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = dict(metrics)
        metrics["total_loss"] = total
        metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    jitted = jax.jit(step_fn, in_shardings=(state_sh, None),
                     out_shardings=(state_sh, None), donate_argnums=(0,))

    def step(state, batch):
        batch = {k: jax.device_put(v, batch_sh) for k, v in batch.items()}
        return jitted(state, batch)

    step._jitted = jitted
    return step
