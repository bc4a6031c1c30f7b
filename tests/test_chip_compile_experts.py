"""The serve programs of the kinds with dropless experts, compiled for a
described TPU v5e at their cells' sizes (``tests/chip_compile.py`` has the
how and the why; a kind's row of ``tests/kinds.py`` has its cell, its
programs' limits and what may not leave its stacks).

- ``xing4_0``, the long-context cell's model: 1 dense + 6 expert layers at
  published widths, latent attention and four residual streams, 33 slots of
  8,192.  11.08 GB of weights and a 2.18 GB latent cache leave the largest
  prefill program 1.7 GB: no copy of the cache (the compiler, left alone,
  carries it through the loops positions-minor-most, 2.2 GB in and out of
  every admit) and no layer's 1.4 GB of experts sliced out of their stack may
  sit among the temporaries.
- ``solar_open2``, the reasoning cell's: one period (gated GQA + 3 KDA) at
  published widths with 40 of 320 dropless experts under every layer, 64 + 1
  slots of 4,096.  6.62 GB of weights, 1.09 GB of K/V for the one GQA layer
  and 0.82 GB of float32 state; neither stack may be copied, no layer's 1.26
  GB of experts sliced out of their stack, and the state not sliced a layer
  at a time.
- ``nemotron_h``, the state-space cell's: Nemotron-3-Nano's first nine
  layers, each a sublayer alone (4 state-space, 4 expert layers of 64 held
  experts of two matrices, 1 attention layer of 2 KV heads), 64 slots of
  8,192: the state [4, 65, 64, 64, 128] float32 and the K/V rows updated in
  place, the experts never out of their stack.
- ``phi4flash`` (no experts; here for the same reasons as Granite's): Phi-4-
  mini-flash-reasoning whole, 32 layers in three segments, 64 slots of
  8,192: 7.70 GB of weights, rows of ONE layer (2.7 GB), rings of eight, the
  state [9, 65, 16, 40, 128] float32 updated in place by a kernel call a
  segment.
- ``granitemoehybrid`` (no experts; here because its row has
  ``cell_programs`` and its check's prefill is Nemotron's sibling):
  granite-4.0-h-micro whole, 40 layers in four periods of nine state-space
  layers of one 64-head group and one attention layer, a dense MLP under
  each, 64 slots of 4,096: 6.38 GB of weights, the state [36, 65, 64, 64,
  128] float32 (4.9 GB) carried through the scan over periods and updated
  in place by nine kernel calls a period, 2.2 GB of K/V rows.
"""

import re

import jax
import jax.numpy as jnp
import pytest

import kinds
from chip_compile import (KERNEL, _cell_program, _compile, _on,  # noqa: F401
                          as_tpu, copies_of, in_place, one_chip, shapes_on,
                          topo, whole_row_programs)
from ray_tpu.models import decode

CELL_PROGRAMS = [(k.name, *p) for k in kinds.KINDS.values()
                 for p in k.cell_programs]


@pytest.mark.parametrize("name,program,temp_gb,kernels", CELL_PROGRAMS,
                         ids=[f"{n}-{p}" for n, p, *_ in CELL_PROGRAMS])
def test_the_kinds_cell_program_fits_and_updates_its_cache_in_place(
        one_chip, as_tpu, name, program, temp_gb, kernels):
    """Under 15.0 GiB, as the kind's issue asked of its largest program, the
    temporaries under the row's limit, the row's kernel calls, its stacks
    updated where they lie and nothing it names sliced out of a stack."""
    row = kinds.KINDS[name]
    compiled, text = _cell_program(one_chip, name, program)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_gb * 1e9, mem.temp_size_in_bytes
    total = in_place(mem)
    assert total < 15.0 * 2**30, f"{total / 2**30:.2f} GiB"
    assert text.count(KERNEL) == kernels
    for stack in row.stacks:
        assert stack in text and not copies_of(stack, text)
    for leaves_its_stack in row.held_in_place:
        assert not re.search(leaves_its_stack, text)


def test_latent_8192_program_walks_a_row_in_chunks_of_2048(one_chip, as_tpu):
    """PR 47: of the latent cell's five buckets the 8,192 alone has four
    chunks of the length its experts ask for (128 rows an expert of 64, 4 a
    token), so its program is the loop over a row's chunks: the latent kind's
    ``continued_attention`` through the forward kernel with a query offset,
    keys of 192 and values of 128 a head's rows apart, rebuilt a layer a
    chunk from the slot's latent rows and never laid out anew on the way to
    the kernel.  It left ``WHOLE_ROW_PROGRAMS`` for this test."""
    from ray_tpu.ops.flash_attention import KERNEL_FLASH_ROWS
    from ray_tpu.ops.moe import KERNEL_MOE_GMM as KERNEL_GMM
    cfg = kinds.cell_cfg("xing4_0")
    cache = jax.eval_shape(lambda: decode.init_kv_cache(cfg, 33, 8192))
    assert [decode.prefill_width(cache, b, cfg)
            for b in (512, 1024, 2048, 4096, 8192)] == [
                512, 1024, 2048, 4096, 2048]
    compiled, text = _cell_program(one_chip, "xing4_0", "prefill-8192")
    # reading 533,731,840 (the whole row: 1,711,136,256)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    # the kernels it now has: the offset kernel for the dense layer and for
    # the scan's body, no whole-row flash_fwd, and the two grouped matmuls
    names = re.findall(r'custom-call\(.*?"tpu_custom_call".*?op_name="[^"]*?'
                       r'/(\w+)/pallas_call"', text)
    assert sorted(names) == sorted([KERNEL_FLASH_ROWS] * 2 + [KERNEL_GMM] * 2)
    # a chunk of 2,048 tokens a pass; rows, chunks and layers are loops, and
    # so is the prefix's rebuilding, in the dense layer and in the body
    assert "s32[1,2048]" in text and "f32[1,2048,4,3584]" in text
    assert "f32[1,8192,4,3584]" not in text
    assert len(re.findall(r" while\(", text)) >= 5
    # the rebuilt rows [32 heads, 8192, 192 | 128] are carried as the kernel
    # reads them: no transposing copy before a call
    assert "bf16[32,8192,192]{2,1,0" in text
    assert not re.search(r"= bf16\[(1,)?32,8192,(192|128)\]\S* "
                         r"(copy|transpose)\(", text)


@pytest.mark.parametrize("name,calls,kernels", [
    ("nemotron_h", 4 + 1 + 2 * 4, ("flash_fwd", "ssd_chunk_fwd", "moe_gmm")),
    ("granitemoehybrid", 9 + 1, ("flash_fwd", "ssd_chunk_fwd")),
    # (a chunked scan a segment with a Mamba layer, the band's forward, the
    # full layer's; the cross-decoder's one token takes no kernel)
    ("phi4flash", 2 + 1 + 1, ("flash_fwd", "flash_window_prefill",
                               "selective_scan_chunk_fwd"))])
def test_the_state_space_checks_prefill_runs_a_buckets_kernels(
        one_chip, as_tpu, name, calls, kernels):
    """The configuration's ``check`` compares a prefill and decode steps
    through the kind's entry points with the reference on its own.  Its
    prompt's length is no multiple of a chunk, and the kind's ``prefill``
    pads the row to whole blocks as the engine's admits are padded to its
    buckets: the compared prefill is the 2,048 bucket's row, with the flash
    kernel (from 1,024 positions up, whole blocks of 512), a chunked scan a
    state-space layer (of a period's: the scan over periods traces one) and
    two grouped products an expert layer in it; a row of the prompt's own
    length would run plain attention."""
    kind, doc = kinds.load(name), kinds.cell_doc(name)
    cfg, chk = kinds.cell_cfg(name), doc["serve"]["check"]
    n_prompt, n_dec = chk["prompt_len"], chk["decode_steps"]
    bucket = -(-n_prompt // kind.ROW_BLOCK) * kind.ROW_BLOCK
    cache_len = -(-(n_prompt + n_dec + 1) // 128) * 128
    assert n_prompt % 128 and n_dec >= 256
    assert 1024 <= bucket <= cache_len and bucket in doc["serve"]["buckets"]
    S = shapes_on(one_chip)
    params = _on(one_chip, jax.eval_shape(
        lambda k: kind.init_params(k, cfg, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(lambda: kind.init_cache(
        cfg, 1, cache_len, jnp.bfloat16)))
    # as ``serve_app._check_reference`` jits it
    _, text = _compile(
        lambda p, c, t, ln, sl: kind.prefill(p, c, t, ln, sl, cfg),
        params, cache, S((1, n_prompt), jnp.int32), S((1,), jnp.int32),
        S((1,), jnp.int32))
    assert text.count(KERNEL) == calls
    for kernel in kernels:
        assert kernel in text, kernel
    assert f"s32[1,{bucket}]" in text


# ---------- the programs that walk whole rows are the parent's (PR 37)
# The latent kind's 4096 read equal too at PR 37; it is left out for the
# minute its compile takes.  The latent kind's three were pinned anew at PR
# 39 (the expert layer's combine one body, the expanded attention one
# helper), its decode at PR 44 (one walk over the layers: the same
# temporaries to the byte) and its prefill at PR 45 (keys of 192 and values
# of 128 as they are: the 2,048 program's temporaries fell 4.0%); the 8,192
# left this list at PR 47, whose rows are walked in chunks of 2,048 (its own
# test above).

# Granite's two were pinned at PR 53, which added them (of each, 1,255,145,472
# are the chip's copy of the ``W_in`` stack: ``kinds.py``, the row's note);
# its decode anew at PR 55, whose recurrent step moves two whole slots a grid
# step (``ssd.step_block``): 516,096 bytes more, 0.04%.

test_whole_row_programs_are_the_parents = whole_row_programs({
    ("xing4_0", "decode", None): (163378176, 4, 6),
    ("xing4_0", "prefill-2048", None): (459842048, 4, 3),
    ("granitemoehybrid", "decode", None): (1308068864, 10, 4),
    ("granitemoehybrid", "prefill-4096", None): (1799708160, 10, 2),
    # SambaY's five, pinned at PR 60, which added them: decode's kernels are
    # a selective-scan step a segment with a Mamba layer, the ring's, and
    # decode_attn for the full layer and for a cross layer, its loops the
    # steps and the three segments' (and the kernels' own); an admit's are a
    # chunked scan a segment, the band's forward and the full layer's from
    # 1,024 positions up, its loops the rows and the two segments of the
    # self-decoder (the cross-decoder's one segment of one token beside)
    ("phi4flash", "decode", None): (238315520, 5, 7),
    ("phi4flash", "prefill-512", None): (182522880, 2, 3),
    ("phi4flash", "prefill-1024", None): (282081792, 4, 3),
    ("phi4flash", "prefill-2048", None): (418019328, 4, 3),
    ("phi4flash", "prefill-4096", None): (631769600, 4, 3),
})


def test_sambays_programs_hold_the_cells_arguments(one_chip, as_tpu):
    """PR 60: the decode program and the four admits hold the same 12.0 GB
    of arguments (7.70 GB of weights, 2.73 of rows, 1.36 of rings, 0.21 of
    state and tails; an admit its rows of tokens besides), 75% of the chip's
    16e9, and none copies the convolution tails' stack either in decode."""
    sizes = {p: _cell_program(one_chip, "phi4flash", p)[0].memory_analysis()
             .argument_size_in_bytes
             for p, *_ in kinds.KINDS["phi4flash"].cell_programs}
    assert sizes["decode"] == 12_006_397_952
    assert all(0 < v - sizes["decode"] < 1 << 20 for p, v in sizes.items()
               if p != "decode")
    assert 0.74 < sizes["decode"] / 16e9 < 0.76
    _, text = _cell_program(one_chip, "phi4flash", "decode")
    assert not copies_of("bf16[9,65,3,5120]", text)
