"""On the chips (``chiprun --chips 4 -- python tests/chip_mellum_check.py
[seed ...] [variant ...] [aot=DIR] [seconds=N]``; not a pytest file: the
tests here are held to the CPU).  ``train-swa-moe-ep4-s8192``'s own
configuration (``mellum2-12b-a2.5b-train-l4``: one period at published
widths, 64 experts 16 a chip, the whole vocabulary, one 8,192-token sequence
a chip) on the cell's mesh of ``ep`` = 4 in bf16, held to the block kind's
float32 reference (``benchmark/models/mellum.py``, computed in blocks) in
two comparisons, because the cell's scalar first-step loss can see neither
the experts nor a precision (PERF.md section 7).

**The step**: ``causal_lm_loss`` as the train step differentiates it: the
total loss and EVERY GRADIENT LEAF's relative L2 against ``jax.grad`` of
``total_loss``, the reference TOLD the experts the compared run chose
(``follow``: data of the run, as ``tests/chip_nano_check.py``'s ``told``),
on PLAIN drawn weights (``transformer.init_params``, the draw a training run
starts from), not the cell's sharpened ones.  Why both, read on the CPU at a
middle size (hidden 256, 16 experts 4 a token, 4 x 512 tokens, three seeds;
PERF.md section 6, PR 58, second round): the program in float32 is the
reference to 1e-5; a bf16 run and the reference on its own stand 0.21-0.26
apart in the worst leaf on the plain draw and 0.98 on the sharpened one.
Told, the plain draw reads 0.033-0.040: the rest was the routers'
near-ties, one expert's whole output for another's at a token in a hundred.
The sharpened draw still reads 0.46 told: its attention (scores of spread 4
and more) turns a layer's bf16 rounding of 1.4% into 6%, 25%, 33% of the
hidden state over the next three layers, so nothing a layer does wrong can
be told from what it hands on.  A gradient of zeros reads exactly 1 on
every leaf.  The controls, which have to read over ``LIMITS`` by the loss
or by the worst leaf:

- ``part_left_out``: one chip's part of every expert layer left out of the
  combine (holder 1 adds nothing);
- ``band_1152``: the window layers read 1,152 positions for 1,024;
- ``yarn_all``: YaRN's table on the window layers too.

**The layer** (``layer_*``): the step's bf16 noise (3% of a gradient leaf)
hides a precision lost inside one product (0.3%), so ONE expert layer
through the exchange on the four chips, ``_dropless_block`` on drawn hidden
states [4, 8192, 2304] with the first layer's router and experts: its
output and the gradients of the input, the router and the three stacks
against the reference's layer (``experts_layer``, float32, told the
routing, on the same bf16-rounded operands), each reading a mean over 75
million elements and more.  Held to ``LIMITS["layer_out"]`` by the output
and to ``LIMITS["layer_grad"]`` by the worst of the five gradients; the
lower precisions have to read over one of them:

- ``layer_combine_bf16``: the combine's sum over a token's assignments
  rounded to bf16 after every addition;
- ``layer_gmm_bf16``: the grouped products' contraction summed in a bf16
  accumulator, an eighth of the contraction at a time
  (``jax.lax.reduce_precision`` between the kernel's calls).

**What the chips read** (my chip run, PR 58, second round, call h, seeds
3000000017 / 19 / 23; ``chiprun_out/pr58h/``; ``tests/test_mellum_grads.py``
holds ``LIMITS`` to these rows).  The layer, three seeds: output 0.0045530 /
0.0045533 / 0.0045533, the worst gradient (the input's) 0.005332 / 0.005324 /
0.005328; ``layer_gmm_bf16`` 0.008066 and 0.008113 (the router's), over both
limits; ``layer_combine_bf16`` 0.005315 on the output, over its limit, and
the sound readings on the gradients (the rounded sum has the plain one's
transposes).  The step, TWO seeds: worst leaf 0.02732 / 0.02862 (a window
layer's ``wq`` / ``k_norm``; the experts' stacks 0.0221-0.0237, ``lm_head``
0.0129, ``final_norm`` 0.0041), total loss 1.9e-6 / 1.8e-5 from the
reference's, the run's choices the same in both of its runs.  **Not read on
the chips under this comparison: the third seed, ``yarn_all``,
``part_left_out``, ``band_1152``**: the round's forty chip-minutes ended in
the third seed's reference (97 s a seed where I reckoned 30).  The first
round's call g read the last two against the reference on its own
(sharpened draw: 1.31-1.42 for the sound program's 0.79-1.05); at the tiny
size in float32 and at the middle size in bf16 every one of the three
stands at 0.77-2.6 for the sound program's 2e-6 and 0.03-0.04.

One JSON line a (variant, seed), then ``MELLUMCHECK {...}``; exits 1 where
a sound reading fails or a control passes.  ``tiny`` first: the tests' toy
configuration in float32 on four virtual CPU devices, held to
``TINY_LIMITS`` (``tests/test_mellum_grads.py`` runs it; the grouped
products' control is mute there).  The sound variants run on every seed
given, the controls on the first; a run that raises is reported and the
next one tried.

Times on the chips (call h): a layer program compiles in 23-30 s and a run
with its reference takes 24 s; the step's program compiles in 89 s, and a
seed takes 97 s, most of it the reference's 8,192 tokens a chip through all
64 experts in float32.  ``seconds=N``: no run is started that the slowest
so far would carry past N seconds (a command killed at ``chiprun``'s limit
in mid-program left the chip unanswering: a strike).  ``save=DIR`` (here,
no chip): every program compiled for a described ``v5e:2x2`` and written
to DIR with its ``memory_analysis()``; ``aot=DIR`` (on the chips): a
program found there is loaded, not compiled, and compiled where loading
fails.  Call h loaded the two references and the two comparisons so (127
MB; 62 + 16 s of compiling here); a step program is 488 MB and a layer
program 95-109 MB, past what a call's copy may hold.  A directory is as
new as the code it was written from: write it anew after an edit of the
reference or the program."""

import contextlib
import dataclasses
import json
import os
import pickle
import sys
import time
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "mellum2-12b-a2.5b-train-l4.json")
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "configs",
                    "tiny-mellum.json")
STEP = ("sound", "yarn_all", "part_left_out", "band_1152")
LAYER = ("layer_sound", "layer_gmm_bf16", "layer_combine_bf16")
VARIANTS = STEP + LAYER
MUST_FAIL = ("part_left_out", "band_1152", "yarn_all", "layer_gmm_bf16",
             "layer_combine_bf16")
#: the step: |total loss - reference|, the largest relative L2 of a gradient
#: leaf; the layer: the relative L2 of its output, the largest of its five
#: gradients'.  Each between what the sound program read on the chips and
#: the least reading of a fault (the docstring): the loss 1.8e-5, with no
#: fault's reading above it on the chips yet (the first round's 5.7e-3 was
#: the sharpened cell's scalar, a draw of standard error 2.8e-3 and no
#: fault seen: PERF.md section 6, PR 58, third round); a leaf 0.0286 and a
#: gradient of zeros' 1; the layer's output 0.004553 and the rounded combine's 0.005315; its
#: gradients 0.005332 and the rounded products' 0.008113
LIMITS = {"loss": 1e-3, "leaf": 0.1, "layer_out": 0.0049,
          "layer_grad": 0.0065}
#: in float32 at the tiny size the program is the reference to 1e-5
TINY_LIMITS = {"loss": 1e-4, "leaf": 1e-3, "layer_out": 1e-4,
               "layer_grad": 1e-4}


def passes(held: dict, limits: dict) -> bool:
    """Whether every reading of ``held`` lies within its limit."""
    return all(0 <= held[name] <= limits[name] for name in held)


def bf16(x):
    import jax
    import jax.numpy as jnp
    return jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7)


def combine_bf16(moe):
    """``moe._combine`` with every addition rounded to bf16 (its transposes
    its own), and the sum of the holders' parts rounded likewise."""
    import jax

    def fwd(ys, gates, mine, source, dest):
        import jax.numpy as jnp
        gates = jnp.where(mine, gates, 0.0)
        out = None
        for j in range(dest.shape[1]):
            term = bf16(gates[:, j, None] * moe._picked(ys, dest, j))
            out = term if out is None else bf16(out + term)
        return out

    rounded = jax.custom_vjp(fwd)
    rounded.defvjp(lambda *a: (fwd(*a), moe._combine_fwd(*a)[1]),
                   moe._combine_bwd)
    # the same rounding where the layer walks its layout's live rows (PR
    # 59: the cell's blocks do), the walk's transposes its own
    walked = jax.custom_vjp(lambda *a: fwd(*a[:5]), nondiff_argnums=(6,))
    walked.defvjp(lambda *a: (fwd(*a[:5]), moe._combine_live_fwd(*a)[1]),
                  moe._combine_live_bwd)
    return rounded, walked


def gmm_bf16(moe, parts=8):
    """``moe._gmm_pallas`` of one matrix with its contraction in ``parts``
    calls of the kernel, the running sum rounded to bf16 between them."""
    real = moe._gmm_pallas

    def gmm(x, weights, layer, tile_expert, tiles, tile, interpret,
            activation=None, transposed=False):
        k = x.shape[1]
        if len(weights) != 1 or activation or transposed or k % parts:
            return real(x, weights, layer, tile_expert, tiles, tile,
                        interpret, activation, transposed)
        acc, step = None, k // parts
        for i in range(parts):
            part = real(x[:, i * step:(i + 1) * step],
                        (weights[0][:, :, i * step:(i + 1) * step],), layer,
                        tile_expert, tiles, tile, interpret)
            acc = bf16(part) if acc is None else bf16(acc + bf16(part))
        return acc.astype(x.dtype)

    return gmm


def part_left_out(moe):
    real = moe._held_part

    def held(*a, **kw):
        import jax
        out, sizes = real(*a, **kw)
        return out * (jax.lax.axis_index("ep") != 1), sizes

    return held


@contextlib.contextmanager
def patched(name, cfg):
    """The program under variant ``name``: (its configuration, patches
    held)."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import moe
    if name == "part_left_out":
        with mock.patch.object(moe, "_held_part", part_left_out(moe)):
            yield cfg
    elif name == "layer_combine_bf16":
        rounded, walked = combine_bf16(moe)
        with mock.patch.object(moe, "_combine", rounded), \
                mock.patch.object(moe, "_combine_live", walked):
            yield cfg
    elif name == "layer_gmm_bf16":
        with mock.patch.object(moe, "_gmm_pallas", gmm_bf16(moe)):
            yield cfg
    elif name == "band_1152":
        yield dataclasses.replace(
            cfg, sliding_window=cfg.sliding_window * 9 // 8)
    elif name == "yarn_all":
        with mock.patch.object(
                transformer, "rope_table",
                lambda c, kind, real=transformer.rope_table: real(c, "full")):
            yield cfg
    else:
        yield cfg


def main(argv):
    tiny = "tiny" in argv
    seeds = [int(a) for a in argv if a.isdigit()] or [3_000_000_017]
    # (on the CPU the grouped products are the kernel's twin: mute)
    variants = [a for a in argv if a in VARIANTS] or [
        v for v in VARIANTS if not (tiny and v == "layer_gmm_bf16")]
    dirs = {k: v for k, _, v in (a.partition("=") for a in argv) if v}
    save, aot = dirs.get("save"), dirs.get("save") or dirs.get("aot")
    deadline, slowest = float(dirs.get("seconds", "inf")), 0.0
    with open(TINY if tiny else CONFIG) as f:
        doc = json.load(f)
    from ray_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import serialize_executable
    from jax.sharding import PartitionSpec as P
    from benchmark.lib.manifest import load_model
    from ray_tpu.models import sharding as shard_rules
    from ray_tpu.models import transformer
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.parallel.mesh import named_sharding

    F32 = jnp.float32
    model = load_model(os.path.join(REPO, "benchmark", "models", "mellum.py"))
    cfg, tr = model.program_config(doc), doc["train"]
    if save:
        # the chips described, not attached; dispatch that asks the backend
        # is told the one the programs are compiled for
        from jax.experimental import topologies
        devs = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
        jax.default_backend = lambda: "tpu"
        os.makedirs(save, exist_ok=True)
    else:
        devs = jax.devices()[:4]
    mesh = MeshSpec(**tr["mesh"]).build(devs)
    over = shard_rules.batch_axes(cfg)
    sharded = lambda *spec: jax.sharding.NamedSharding(mesh, P(*spec))
    param_sh = named_sharding(mesh, shard_rules.logical_param_specs(cfg))
    batch_sh = named_sharding(mesh, shard_rules.batch_spec(cfg))
    rows_sh, whole = sharded(over, None, None), sharded()
    b, s = tr["global_batch"], tr["sequence_length"]
    k, layers = cfg.experts_per_token, cfg.num_layers
    dtype = F32 if tiny else jnp.bfloat16
    pctx = transformer.ParallelContext(mesh=mesh, batch_axes=over)
    t_start = time.monotonic()

    def say(msg):
        print(f"[check +{time.monotonic() - t_start:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def shape(dims, dt, sh):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sh)

    def built(name, jitted, *args):
        """``jitted`` compiled for ``args`` (shapes with their shardings),
        or loaded from ``aot`` where it lies there."""
        path = os.path.join(aot, name + ".pkl") if aot else None
        if path and not save and os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    exe = serialize_executable.deserialize_and_load(
                        *pickle.load(f), execution_devices=devs)
                say(f"{name}: loaded")
                return exe
            except Exception as e:   # the chip's compiler is the way back
                say(f"{name}: {path} does not load ({e!r}); compiling")
        if save and os.path.exists(path):
            return None                      # written by an earlier call
        t0 = time.monotonic()
        exe = jitted.lower(*args).compile()
        say(f"{name}: compiled in {time.monotonic() - t0:.0f} s")
        if save:
            with open(path, "wb") as f:
                pickle.dump(serialize_executable.serialize(exe), f)
            mem = exe.memory_analysis()
            say(f"{name}: arguments {mem.argument_size_in_bytes / 1e9:.2f} "
                f"+ outputs {mem.output_size_in_bytes / 1e9:.2f} + "
                f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB a chip, "
                f"{os.path.getsize(path) / 1e6:.0f} MB written")
        return exe

    # ---- shapes: the parameters, a batch, a run's choices, a layer's parts
    p_shapes = jax.tree.map(
        lambda a, sh: shape(a.shape, a.dtype, sh), jax.eval_shape(
            lambda: transformer.init_params(jax.random.PRNGKey(0), cfg,
                                            dtype=F32)), param_sh)
    t_shape = shape((b, s + 1), jnp.int32, batch_sh)
    told_shape = shape((b, layers, s, k), jnp.int32,
                       sharded(over, None, None, None))
    moe_sh = {"small": {"router": whole, "bias": whole},
              "experts": {n: sharded("ep", None, None)
                          for n in ("w_gate", "w_in", "w_out")}}
    # a layer's output and the gradients of its input, router and experts
    parts_sh = (rows_sh, rows_sh, moe_sh["small"], moe_sh["experts"])

    def first_layer(p):
        """The first layer's router and experts out of the tree."""
        return {"small": jax.tree.map(
                    lambda a: a[0, 0], p["blocks"][cfg.layer_pattern[0]]["moe"]),
                "experts": jax.tree.map(lambda a: a[0],
                                        p["blocks"]["experts"])}

    m_shapes = jax.tree.map(lambda a, sh: shape(a.shape, a.dtype, sh),
                            jax.eval_shape(first_layer, p_shapes), moe_sh)
    y_shape = shape((b, s, cfg.hidden_size), dtype, rows_sh)

    # ---- the programs
    def step_program(cfg_v):
        def total(p, t):
            seen = []

            def spy(aux, real=transformer._trunk_aux):
                seen.append(aux["moe_choices"])   # [layers, B, S, k]
                return real(aux)

            with mock.patch.object(transformer, "_trunk_aux", spy):
                loss = transformer.causal_lm_loss(
                    p, {"tokens": t[:, :-1], "targets": t[:, 1:]}, cfg_v,
                    pctx, remat=tr["remat"], **(
                        {"compute_dtype": F32, "loss_chunk": None}
                        if tiny else {}))[0]
            return loss, jnp.swapaxes(seen[0], 0, 1)

        return jax.jit(jax.value_and_grad(total, has_aux=True),
                       in_shardings=(param_sh, batch_sh),
                       out_shardings=((whole, told_shape.sharding), param_sh))

    step_reference = jax.jit(jax.value_and_grad(lambda p, t, told: jax.vmap(
        lambda seq, f: model.total_loss(p, seq, doc, f))(t, told).mean()),
        in_shardings=(param_sh, batch_sh, told_shape.sharding),
        out_shardings=(whole, param_sh))

    def rel(got, want):
        d = (got.astype(F32) - want.astype(F32)).reshape(-1)
        return jnp.sqrt(jnp.vdot(d, d) / (jnp.vdot(want, want).astype(F32)
                                          + 1e-30))

    def layer_program(cfg_v):
        def run(y, m, cot):
            def f(y, small, experts):
                out, aux = transformer._dropless_block(
                    y, {**small, **experts}, cfg_v, pctx)
                return (out.astype(F32) * cot).sum(), (
                    out, aux["moe_choices"])
            (_, (out, told)), grads = jax.value_and_grad(
                f, argnums=(0, 1, 2), has_aux=True)(
                    y, m["small"], m["experts"])
            # (the input is in the program's dtype, and so is its gradient)
            return jax.tree.map(lambda a: a.astype(F32),
                                (out,) + grads), told

        return jax.jit(run, in_shardings=(rows_sh, moe_sh, rows_sh),
                       out_shardings=(parts_sh, rows_sh))

    def layer_reference(y, m, cot, told):
        """The reference's layer on the operands as the program multiplies
        them (rounded to its dtype, then float32)."""
        def f(y, small, experts):
            out = jax.vmap(lambda seq, ch: model.experts_layer(
                seq, small["router"], experts, doc, ch))(y, told)
            return (out * cot).sum(), out
        rounded = jax.tree.map(lambda a: a.astype(dtype).astype(F32),
                               m["experts"])
        (_, out), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(
                y.astype(F32), m["small"], rounded)
        return (out,) + grads

    layer_reference = jax.jit(layer_reference, in_shardings=(
        rows_sh, moe_sh, rows_sh, rows_sh), out_shardings=parts_sh)
    told3 = shape((b, s, k), jnp.int32, rows_sh)
    cot_shape = shape((b, s, cfg.hidden_size), F32, rows_sh)
    parts_shapes = jax.tree.map(
        lambda a, sh: shape(a.shape, a.dtype, sh), jax.eval_shape(
            layer_reference, y_shape, m_shapes, cot_shape, told3), parts_sh)
    programs = {}

    def program(name):
        if name not in programs:
            with patched(name, cfg) as cfg_v:
                programs[name] = built(
                    name, layer_program(cfg_v), y_shape, m_shapes, cot_shape
                ) if name in LAYER else built(
                    name, step_program(cfg_v), p_shapes, t_shape)
        return programs[name]

    def step_parts():
        """The step's reference and comparison, built at their first use."""
        if "step" not in programs:
            programs["step"] = (
                built("step_reference", step_reference, p_shapes, t_shape,
                      told_shape),
                built("step_compare", jax.jit(
                    lambda got, want: jax.tree.map(rel, got, want)),
                    p_shapes, p_shapes))
        return programs["step"]

    def layer_parts():
        if "layer" not in programs:
            programs["layer"] = (
                built("layer_reference", layer_reference, y_shape, m_shapes,
                      cot_shape, told3),
                built("layer_compare", jax.jit(
                    lambda got, want: jax.tree.map(rel, got, want)),
                    parts_shapes, parts_shapes))
        return programs["layer"]

    if save:
        for name in variants:
            (layer_parts if name in LAYER else step_parts)()
            program(name)
        return 0

    # ---- the runs
    init = jax.jit(lambda key: transformer.init_params(key, cfg, dtype=F32),
                   out_shardings=param_sh)
    draw = jax.jit(lambda key, p: (
        jax.random.normal(key, y_shape.shape, F32).astype(dtype),
        first_layer(p),
        jax.random.normal(jax.random.fold_in(key, 1), y_shape.shape, F32)),
        out_shardings=(rows_sh, moe_sh, rows_sh))
    limits = TINY_LIMITS if tiny else LIMITS
    rows = []

    def leaves(tree):
        return {jax.tree_util.keystr(path): float(v) for path, v in
                jax.tree_util.tree_leaves_with_path(jax.device_get(tree))
                # the unused selection bias: zero on both sides
                if "bias" not in jax.tree_util.keystr(path)}

    drawn = {}

    def draw_step(seed):
        """(key, parameters, tokens) of ``seed``; one seed's at a time."""
        if seed not in drawn:
            drawn.clear()
            key = jax.random.PRNGKey(seed % 2**31)
            toks = np.random.default_rng([seed, 0]).integers(
                0, cfg.vocab_size, size=(b, s + 1), dtype=np.int32)
            drawn[seed] = key, init(key), jax.device_put(toks, batch_sh)
        return drawn[seed]

    for name in variants:
        for seed in seeds[:1] if name in MUST_FAIL else seeds:
            if time.monotonic() - t_start + slowest > deadline:
                say(f"{seed} {name}: not started, {slowest:.0f} s would end "
                    f"past {deadline:.0f}")
                continue
            key, params, toks = draw_step(seed)
            t0 = time.monotonic()
            row = {"variant": name, "seed": seed}
            try:
                if name in LAYER:
                    reference, compare = layer_parts()
                    y, m, cot = draw(key, params)
                    got, told = program(name)(y, m, cot)
                    rel_l = leaves(compare(got, reference(y, m, cot, told)))
                    del got, y, m, cot
                    out = rel_l.pop("[0]")
                    held = {"layer_out": out}
                    rel_l["output"] = out
                else:
                    # the run's choices first, then the reference told them,
                    # then the run again for its gradient: the reference's
                    # temporaries leave no room beside a second gradient
                    reference, compare = step_parts()
                    told = program(name)(params, toks)[0][1]
                    want, g_want = reference(params, toks, told)
                    (loss, again), g_got = program(name)(params, toks)
                    row["told_same"] = float((again == told).mean())
                    rel_l = leaves(compare(g_got, g_want))
                    del g_got, g_want
                    held = {"loss": abs(float(loss) - float(want))}
            except Exception as e:      # the next variant may still fit
                say(f"{seed} {name}: {e!r}")
                rows.append(dict(row, error=repr(e)[:2000], passes=None))
                print(json.dumps(rows[-1]), flush=True)
                continue
            worst = max((n for n in rel_l if n != "output"), key=rel_l.get)
            held["layer_grad" if name in LAYER else "leaf"] = rel_l[worst]
            row.update(held=held, worst_at=worst,
                       leaves={n: round(v, 6) for n, v in rel_l.items()},
                       seconds=round(time.monotonic() - t0, 1),
                       passes=passes(held, limits))
            rows.append(row)
            slowest = max(slowest, row["seconds"])
            print(json.dumps(row), flush=True)
            say(f"{seed} {name}: {held}, worst at {worst}; "
                f"passes={row['passes']}")
    ok = all(r["passes"] is (r["variant"] not in MUST_FAIL) for r in rows)
    out = {"ok": bool(ok), "limits": limits, "seeds": seeds,
           "device": devs[0].device_kind, "count": len(devs),
           "readings": {n: [dict(r.get("held", {}), passes=r["passes"])
                            for r in rows if r["variant"] == n]
                        for n in variants}}
    print("MELLUMCHECK " + json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
