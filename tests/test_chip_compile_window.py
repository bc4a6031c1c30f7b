"""The window kind's kernels and the K-EXAONE cell's programs, compiled for
a described TPU v5e at the cell's sizes (``tests/chip_compile.py`` has the
how and the why): 64 query heads over 8 KV heads of 128, 48 + 1 slots of
6,144, rings of 256 rows under a window of 128, a verify step of two tokens.

- the ring's decode kernel and the rows' at one token a slot and at two, and
  the banded flash forward, each alone;
- the speculative program (``spec_decode_state_loop`` with the model's own
  block drafting) and the largest admit program: 8.79 GB of weights, 3.7 GB
  of rows and 0.3 GB of rings leave them the temporaries below, under what
  the compiler allows a program on the chip, with every stack updated where
  it lies;
- a dense model's decode kernel is the parent's: one token a slot lowers to
  the same Mosaic call whether or not the kernel knows of several.
"""

import re

import jax
import jax.numpy as jnp
import pytest

import kinds
from chip_compile import (HBM_GIB, KERNEL, _compile, as_tpu,  # noqa: F401
                          copies_of, in_place, one_chip, serve_program,
                          shapes_on, topo)
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import flash_attention as fa

SLOTS, MAX_LEN, RING = 49, 6144, 256


@pytest.mark.parametrize("tokens", [1, 2])
@pytest.mark.parametrize("kernel", ["ring", "rows"])
def test_decode_kernels_compile_at_the_cells_heads(one_chip, kernel, tokens):
    S = shapes_on(one_chip)
    q, live = S((SLOTS, tokens * 64, 128)), S((SLOTS,), jnp.int32)
    if kernel == "ring":
        rows = S((6, SLOTS, RING, 1024))
        fn = lambda q, k, v, n: da.window_decode_attn(  # noqa: E731
            q, k, v, jnp.int32(3), n, 8, 128, tokens, use_kernel=True,
            interpret=False)
    else:
        rows = S((2, SLOTS, MAX_LEN, 1024))
        fn = lambda q, k, v, n: da.decode_attn(  # noqa: E731
            q, k, v, jnp.int32(1), n, 8, use_kernel=True, interpret=False,
            tokens=tokens)
    _, text = _compile(fn, q, rows, rows, live)
    assert text.count(KERNEL) == 1
    # the stack goes to the kernel as it lies: no layer's slab sliced out
    assert not re.search(r"= bf16\[(1,)?49,(256|6144),1024\]\S* "
                         r"(dynamic-slice|copy)\(", text)


def test_banded_flash_forward_compiles_at_the_cells_heads(one_chip):
    S = shapes_on(one_chip)
    _, text = _compile(
        lambda q, k, v: fa.flash_attention(q, k, v, window=128,
                                           interpret=False),
        S((1, 4096, 64, 128)), S((1, 4096, 8, 128)), S((1, 4096, 8, 128)))
    assert text.count(KERNEL) == 1 and fa.KERNEL_FLASH_WINDOW in text


def test_one_token_a_slot_traces_nothing_of_the_several_token_form(
        one_chip, monkeypatch):
    """Mistral's decode call (32 heads over 8 KV heads of 128, 33 slots of
    2,048) and the causal flash forward: at one token a slot and without a
    window the kernels trace none of what the several-token form and the
    band add (their jaxprs equal the parent's to the character: PERF.md
    section 6, PR 50), and compile to one Mosaic call each."""
    def never(*_a, **_k):
        raise AssertionError("the several-token form was traced")
    monkeypatch.setattr(da, "_token_of", never)
    S = shapes_on(one_chip)
    _, text = _compile(
        lambda q, k, v, n: da.decode_attn(q, k, v, jnp.int32(1), n, 8,
                                          use_kernel=True, interpret=False),
        S((33, 32, 128)), S((14, 33, 2048, 1024)), S((14, 33, 2048, 1024)),
        S((33,), jnp.int32))
    assert text.count(KERNEL) == 1
    jaxpr = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, interpret=False))(*(jnp.zeros((1, 1024, h, 128),
                                               jnp.bfloat16)
                                     for h in (32, 8, 8))))
    assert "name=flash_fwd" in jaxpr and "flash_window" not in jaxpr


#: (program, temporaries under, kernel calls, ``in_place`` at or under: the
#: parent's readings at PR 59, sandbox compile; the admit walks its layout's
#: live rows since, one ``moe_rows_blank`` an expert layer's walk in, and
#: reads 16_228_360_192; the speculative step is the parent's program)
CELL_PROGRAMS = (("spec", 1.5, 25, 13_966_164_480),
                 ("prefill-4096", 4.0, 25 + 8, 16_457_051_648))


@pytest.mark.parametrize("program,temp_gb,kernels,held", CELL_PROGRAMS,
                         ids=[p for p, *_ in CELL_PROGRAMS])
def test_the_cells_program_fits_and_updates_its_cache_in_place(
        one_chip, as_tpu, program, temp_gb, kernels, held):
    """What the compiler allows a program on a v5e (15.75 GiB), the
    temporaries under the limit (readings 1.17 and 3.66 GB: sandbox compile,
    PR 50; the 1,024 program reads 2.4), the kernel calls (a period in the
    scan's body and the period walked ahead of it, 4 attention calls and 2 grouped products an
    expert layer each, and the block's 1 + 2), every stack updated where it
    lies and no layer's experts out of their stack."""
    row = kinds.KINDS["exaone_moe"]
    serve = kinds.cell_doc("exaone_moe")["serve"]
    compiled, text = serve_program(
        one_chip, kinds.cell_cfg("exaone_moe"), program,
        serve["num_slots"] + 1, serve["max_len"])
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_gb * 1e9, mem.temp_size_in_bytes
    assert in_place(mem) < HBM_GIB * 2**30, in_place(mem) / 2**30
    assert in_place(mem) <= held, in_place(mem)
    assert text.count(KERNEL) == kernels
    names = set(re.findall(r'op_name="[^"]*?/(\w+)/pallas_call"', text))
    assert names == ({"decode_attn", "window_decode_attn", "moe_gmm"}
                     if program == "spec" else
                     {"flash_fwd", "flash_window_prefill", "moe_gmm",
                      "moe_rows_blank"})
    for stack in row.stacks:
        assert stack in text and not copies_of(stack, text)
    for leaves_its_stack in row.held_in_place:
        assert not re.search(leaves_its_stack, text)
