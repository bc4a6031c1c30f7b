"""The serve programs of the trees without experts, compiled for a described
TPU v5e: llama-400m's, the mistral cell's and the hybrid cell's
(``tests/chip_compile.py`` has the how and the why; the expert kinds' cells
are in ``tests/test_chip_compile_experts.py``)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from chip_compile import (HBM_GIB, KERNEL, STEPS, _admit_rows,  # noqa: F401
                          _cell_program, _compile, _serve_shapes, _slab_ops,
                          as_tpu, copies_of, in_place, one_chip, topo,
                          whole_row_programs)
from ray_tpu.models import config as mcfg
from ray_tpu.models import decode, speculative

LLAMA_400M = mcfg.llama_400m()
SLOTS, MAX_LEN = 17, 1024      # 16 slots + the scratch slot


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_decode_state_loop_compiles(one_chip, paged):
    """The engine's one decode dispatch: 8 steps, cache and state donated."""
    params, cache, state = _serve_shapes(one_chip, LLAMA_400M, paged, SLOTS,
                                         MAX_LEN)
    compiled, _ = _compile(
        lambda p, c, st: decode.decode_state_loop(
            p, c, st, STEPS, LLAMA_400M, 0, jnp.bfloat16),
        params, cache, state, donate_argnums=(1, 2))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_dense_prefill_1024_takes_the_flash_kernel(one_chip, as_tpu):
    """The engine's admit program at the 1024 bucket, batch 8: dense prefill
    reaches the flash kernel through the ``mha`` dispatcher."""
    params, cache, state = _serve_shapes(one_chip, LLAMA_400M, False, SLOTS,
                                         MAX_LEN)
    _, text = _compile(
        lambda p, c, st, *a: decode.prefill_admit(
            p, c, st, *a, LLAMA_400M, 0, jnp.bfloat16),
        params, cache, state, *_admit_rows(one_chip, 1024),
        donate_argnums=(1, 2))
    assert KERNEL in text, "prefill at seq 1024 compiled plain attention"


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_speculative_verify_window_compiles(one_chip, paged):
    """The target's k+1-token verify step of speculative decode (k=4)."""
    params, cache, _ = _serve_shapes(one_chip, LLAMA_400M, paged, SLOTS,
                                     MAX_LEN)
    _compile(
        lambda p, c, t, a: speculative.verify_window(
            p, c, t, a, LLAMA_400M, jnp.bfloat16),
        params, cache,
        jax.ShapeDtypeStruct((SLOTS, 5), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip),
        donate_argnums=(1,))


# ------------------- the serve programs at the benchmark cell's size
#
# Mistral-7B-v0.3 widths, 14 layers, 32 slots + the scratch slot x 2048: what
# ``serve-chat-steady`` and ``serve-decode-saturated`` run.  The stacked cache
# is 1.94 GB each for K and V; a program that passes it through a scan as
# xs/ys slices, restacks and copies it every step and keeps a second copy
# among its temporaries (5.1 GB; 16 layers were refused at 16.26 GiB).

CELL_SLOTS, CELL_MAX_LEN = 33, 2048
# decode: 0.59 GB, the wq and wk stacks transposed once a dispatch (0.47 +
# 0.12 GB, as before PR 30) and no slab of the cache (0.98 GB with two).
# prefill (PR 32: a loop over the admit's real rows, one row a pass): what one
# row needs, 0.002 GB at 256 and 0.24 GB at 2048 (eight rows at once held
# 0.24 / 2.28 GB), beside the same two stacks transposed once a program, which
# the compiler hoists out of the row loop as it does out of decode's step
# loop: readings 0.589 and 0.825 GB.  Since PR 37 the 2048 program walks a row
# in chunks of 512, so what one pass needs is a chunk's and the stacks are
# hoisted out of that loop too: reading 0.591 GB
TEMP_GB = {"decode": 0.7, "prefill-256": 0.6, "prefill-2048": 0.7}


cell_programs = pytest.mark.parametrize("program", list(TEMP_GB))


@cell_programs
def test_cell_program_temporaries(one_chip, as_tpu, program):
    """No second copy of the cache among the temporaries."""
    compiled, _ = _cell_program(one_chip, "mistral", program)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_GB[program] * 1e9, f"{temp / 1e9:.2f} GB"


MISTRAL_KV = (f"bf16[14,{CELL_SLOTS},{CELL_MAX_LEN},1024]",)


@pytest.mark.parametrize("program,model,stacks", [
    *((program, "mistral", MISTRAL_KV) for program in TEMP_GB),
    ("prefill-4096", "olmo_hybrid", kinds.KINDS["olmo_hybrid"].stacks),
], ids=[*(f"{program}-14" for program in TEMP_GB), "prefill-4096-hybrid"])
def test_cell_program_updates_the_cache_in_place(one_chip, as_tpu, program,
                                                 model, stacks):
    """Nothing copies the stacked cache and nothing restacks a layer's slab
    into it, in a loop body or outside one: the only writes to the stack are
    scatters and row-sized ``dynamic-update-slice``s, which alias it.  A
    prefill program carries the stack through its loop over the admit's rows
    the same way."""
    _, text = _cell_program(one_chip, model, program)
    dims_of = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    for stack in stacks:
        assert stack in text
        dims = np.int64(stack[stack.index("[") + 1:-1].split(","))
        slab = np.prod(dims[1:])
        writes = re.findall(
            r"%([\w.\-]+) = " + re.escape(stack)
            + r"\S* (copy|dynamic-update-slice)\(%[\w.\-]+(?:, %([\w.\-]+))?",
            text)
        for name, op, update in writes:
            assert op != "copy", f"%{name} copies the stacked cache"
            assert np.prod(np.int64(dims_of[update].split(","))) < slab, (
                f"%{name} writes [{dims_of[update]}] into the stacked cache")
    if program != "decode":
        # rows, then layers: the outer loop's trip count is the admit's
        # data; a dense tree's bucket of four chunks has the loop over a
        # row's chunks between them, its trip count data too, and one
        # kernel, the forward kernel with a query offset
        chunked = model != "olmo_hybrid" and (
            int(program.split("-")[1]) >= 4 * decode.PREFILL_CHUNK)
        assert len(re.findall(r" while\(", text)) == (3 if chunked else 2)
        if chunked:
            from ray_tpu.ops.flash_attention import KERNEL_FLASH_ROWS
            assert text.count(KERNEL) == 1 and KERNEL_FLASH_ROWS in text
        if model == "olmo_hybrid":  # one row's pass still takes the kernels:
            assert text.count(KERNEL) == 4    # gdn_chunk_fwd x 3, flash_fwd


def test_cell_decode_reads_the_stack_where_it_lies(one_chip, as_tpu):
    """Decode attention is the one Pallas kernel of the layer loop's body
    and no layer's slab leaves the stack on its way to it."""
    _, text = _cell_program(one_chip, "mistral", "decode")
    assert text.count(KERNEL) == 1
    assert not _slab_ops(text, CELL_SLOTS, CELL_MAX_LEN, 1024)


@cell_programs
def test_cell_program_fits_at_16_layers(one_chip, as_tpu, program):
    compiled, _ = _cell_program(one_chip, "mistral", program, num_layers=16)
    total = in_place(compiled.memory_analysis())
    assert total < HBM_GIB * 2**30, f"{total / 2**30:.2f} GiB"


# ------------- layers of two kinds at the benchmark cell's size (PR 29)
#
# Olmo-Hybrid-7B widths, 12 layers (9 gated-delta-rule + 3 full attention),
# 24 slots + the scratch slot x 4096: what ``serve-hybrid-longgen-closed``
# runs.  K and V are 2.36 GB each and the float32 state 0.5 GB (two heads of
# 192 lanes a tile since PR 57; 0.66 GB before, every head padded to 256);
# none of them may be copied, and the state may not be sliced a layer at a
# time either.

HYBRID_SLOTS, HYBRID_MAX_LEN = 25, 4096


def test_hybrid_decode_program_holds_both_states_in_place(one_chip, as_tpu):
    compiled, text = _cell_program(one_chip, "olmo_hybrid", "decode")
    mem = compiled.memory_analysis()
    total = in_place(mem)
    assert total < HBM_GIB * 2**30, f"{total / 2**30:.2f} GiB"
    # no K/V slab of a layer (0.79 GB each) and no period's weights sliced
    # out of their stacks (1.3 GB) among the temporaries
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes / 1e9
    assert not _slab_ops(text, HYBRID_SLOTS, HYBRID_MAX_LEN, 3840)
    # a period's body: one recurrent step a linear layer and decode_attn
    assert text.count(KERNEL) == 4
    for stack in kinds.KINDS["olmo_hybrid"].stacks:
        assert stack in text and not copies_of(stack, text)
    # no layer's [slots, 15, 96, 384] slab is sliced out of the state stack
    assert not re.search(
        rf"= f32\[(1,)?{HYBRID_SLOTS},15,96,384\]\S* (dynamic-slice|copy)\(",
        text)



# ---------- the programs that walk whole rows are the parent's (PR 37)
# The hybrid's 512 / 1024 read equal too at PR 37; they are left out for the
# minute their compiles take: the choice is read off the tree's leaves, not
# the bucket.

# (temporaries in bytes, kernel calls, loops): decode_attn in the step's four
# loops; plain attention under a bucket of 1,024, flash_fwd from there; the
# hybrid's chunked forward a linear layer and flash_fwd from 2,048 up; a
# prefill's loops are its rows and its layers.
test_whole_row_programs_are_the_parents = whole_row_programs({
    ("mistral", "decode", 14): (588719616, 1, 4),
    ("mistral", "prefill-128", 14): (589478400, 0, 2),
    ("mistral", "prefill-256", 14): (589478400, 0, 2),
    ("mistral", "prefill-512", 14): (589478400, 0, 2),
    ("mistral", "prefill-1024", 14): (651342848, 1, 2),
    # (the hybrid's since PR 57, whose row leaves its nine states packed two
    # heads a tile: 88294912 / 275977216 / 731474432 with every head's 192
    # lanes padded to 256 among the temporaries; kernels and loops as before)
    ("olmo_hybrid", "prefill-256", None): (58740224, 3, 2),
    ("olmo_hybrid", "prefill-2048", None): (253629440, 4, 2),
    ("olmo_hybrid", "prefill-4096", None): (678406656, 4, 2),
})
