"""Compile the main path's device programs for a described TPU v5e, no chip:
what ``tests/test_chip_compile_*.py`` share (a module, no tests).

The TPU's compiler is installed where the tests run, and it compiles for a
chip that is described and not attached (``jax.experimental.topologies``).
Interpret mode, which every other kernel test here uses, lowers a Pallas
kernel to plain HLO and so cannot see what the chip's compiler refuses: a
block that does not tile, too much VMEM, a Mosaic call left to the SPMD
partitioner.  Each case of those files is one compile with
``interpret=False`` at the real widths of a model the repo ships, seconds
apiece.  A compile that passes is not a run: nothing there says anything
about results or speed.

The topology is described inside a module-scoped fixture (never at import:
every xdist worker imports these files).  One process at a time may load the
TPU's library unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set, as the driver's
command sets it: under several workers without it the files that reach a
second worker skip (``topo``), in one process all of them run.  The cases
are split by what they compile (the kernels alone; the serve cells' programs,
dense trees and expert trees apart; the train cells' steps), so that no one
file is a run's floor under ``--dist loadfile``; programs that several cases
read are compiled once a process (``_cell_program``), which is why a cell's
cases stay in one file.  Where dispatch asks ``jax.default_backend()`` the
test steers it (``as_tpu``); the program has no option for that.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import kinds
from ray_tpu.models import decode, paged_decode, transformer

KERNEL = "tpu_custom_call"   # how a compiled Pallas kernel shows in the HLO
STEPS = 8
HBM_GIB = 15.75          # what the compiler allows a program on a v5e


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Dispatch that asks the backend sees the chip the program is compiled
    for, not the CPU the test runs on."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(sharding, tree):
    """Shapes of ``tree`` placed by ``sharding`` (one sharding, or a tree)."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, sharding)


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    return compiled, compiled.as_text()


def shapes_on(one_chip):
    """``S(shape, dtype)``: a bf16 (by default) array's shape on the chip."""
    return lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)


def _serve_shapes(one_chip, cfg, paged, slots, max_len):
    params = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    if paged:
        cache = jax.eval_shape(lambda: paged_decode.init_paged_cache(
            cfg, slots * (max_len // 64) // 2, 64, slots, max_len // 64))
    else:
        # (a ring with the margin of the verify step that the model's own
        # block drafts for, as the engine allocates it)
        cache = jax.eval_shape(lambda: decode.init_kv_cache(
            cfg, slots, max_len,
            ring=decode.ring_len(cfg, 2) if cfg.mtp_layers else None))
    state = jax.eval_shape(lambda: decode.init_decode_state(
        slots, jax.random.PRNGKey(1)))
    return _on(one_chip, params), _on(one_chip, cache), _on(one_chip, state)


def _admit_rows(one_chip, bucket, b=8):
    """The engine's admit batch after (params, cache, state): tokens,
    lengths, slot ids, temperatures, budgets, eos ids, real-row mask."""
    row = lambda dt, *shape: jax.ShapeDtypeStruct(  # noqa: E731
        (b,) + shape, dt, sharding=one_chip)
    return (row(jnp.int32, bucket), row(jnp.int32), row(jnp.int32),
            row(jnp.float32), row(jnp.int32), row(jnp.int32),
            row(jnp.bool_))


def serve_program(one_chip, cfg, program, slots, max_len, rows=8):
    """One of the engine's programs ("decode", "spec": the rounds of a
    dispatch with the model's own block drafting, or "prefill-<bucket>" with
    ``rows`` rows an admit), cache and state donated as the engine donates
    them: (compiled, its text)."""
    args = _serve_shapes(one_chip, cfg, False, slots, max_len)
    if program == "decode":
        fn = lambda p, c, st: decode.decode_state_loop(  # noqa: E731
            p, c, st, STEPS, cfg, 0, jnp.bfloat16)
    elif program == "spec":
        from ray_tpu.models import speculative
        fn = lambda p, c, st: speculative.spec_decode_state_loop(  # noqa: E731
            p, c, {}, {}, st, 2, STEPS // 2, cfg,
            speculative.block_drafter(cfg), 0, jnp.bfloat16)
    else:
        args += _admit_rows(one_chip, int(program.split("-")[1]), rows)
        fn = lambda p, c, st, *a: decode.prefill_admit(  # noqa: E731
            p, c, st, *a, cfg, 0, jnp.bfloat16)
    return _compile(fn, *args, donate_argnums=(1, 2))


_cell_compiled = {}


def _cell_program(one_chip, name, program, **changes):
    """``serve_program`` of a kind's cell (``kinds.cell_cfg``, with
    ``changes``) at the cell's own slots, length and rows an admit; compiled
    once a process."""
    key = (name, program, tuple(sorted(changes.items())))
    if key not in _cell_compiled:
        serve = kinds.cell_doc(name)["serve"]
        _cell_compiled[key] = serve_program(
            one_chip, kinds.cell_cfg(name, **changes), program,
            serve["num_slots"] + 1, serve["max_len"],
            serve["engine_kwargs"].get("prefill_batch", 8))
    return _cell_compiled[key]


def in_place(mem):
    """What a program holds on the chip at once, donated arguments counted
    once."""
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def copies_of(stack, text):
    return re.search(r"= " + re.escape(stack) + r"\S* copy\(", text)


def _slab_ops(text, slots, max_len, chan):
    """Instructions whose result is one layer's ``[slots, max_len, chan]``
    K or V slab, sliced or copied out of the stack."""
    return re.findall(
        rf"%[\w.\-]+ = bf16\[(?:1,)?{slots},{max_len},{chan}\]\S* "
        r"(?:dynamic-slice|copy|fusion)\(", text)


def whole_row_programs(pinned):
    """``test_whole_row_programs_are_the_parents`` over a file's ``pinned``
    programs, (kind, program, layers or None) -> (temporaries in bytes,
    kernel calls, loops): only a bucket of four chunks or more of a tree of
    rows alone (K/V; latent since PR 47) compiles to another program, every
    other one keeps what it had at PR 35 (sandbox compiles of both trees, PR
    37): its temporaries to the byte, and the kernels it calls and the loops
    it runs, which say what walks a row.  (The generated code's size was
    pinned too until PR 49: it moves with the Python frames an instruction
    carries, the calling test's among them.)"""
    @pytest.mark.parametrize(
        "name,program,layers", list(pinned),
        ids=[f"{layers or name}-{program}"
             for name, program, layers in pinned])
    def test_whole_row_programs_are_the_parents(one_chip, as_tpu, name,
                                                program, layers):
        compiled, text = _cell_program(
            one_chip, name, program,
            **({"num_layers": layers} if layers else {}))
        assert (compiled.memory_analysis().temp_size_in_bytes,
                text.count(KERNEL), len(re.findall(r" while\(", text))
                ) == pinned[name, program, layers]
    return test_whole_row_programs_are_the_parents
