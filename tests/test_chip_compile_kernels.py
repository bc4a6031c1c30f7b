"""The Pallas kernels alone, compiled for a described TPU v5e at the sizes
the cells run them (``tests/chip_compile.py`` has the how and the why)."""

import functools

import jax
import jax.numpy as jnp
import pytest

import kinds
from chip_compile import (KERNEL, _compile, _slab_ops, copies_of,  # noqa: F401
                          one_chip, shapes_on, topo)
from ray_tpu.models import decode


def _qkv(sharding, b, s, h, kv, d, d_v=None):
    """q, k of ``d`` lanes and v of ``d_v`` (``d`` where None)."""
    return tuple(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
        for shape in ((b, s, h, d), (b, s, kv, d), (b, s, kv, d_v or d)))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [
    pytest.param((8, 2048, 12, 6, 128), id="llama-400m"),
    pytest.param((8, 1024, 12, 12, 64), id="gpt2-124m"),
    # keys of 192 and values of 128 as they are, as the latent kind's prefill
    # and its train step hand them over (PR 45), at the train cell's 8,192
    pytest.param((2, 8192, 16, 16, 192, 128), id="latent-8192"),
])
def test_flash_attention_compiles(one_chip, shape, direction):
    from ray_tpu.ops.flash_attention import flash_attention
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    if direction == "bwd":
        fwd = fn
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    _, text = _compile(fn, *_qkv(one_chip, *shape))
    # forward: one kernel; backward: the forward and the one backward kernel
    assert text.count(KERNEL) == (1 if direction == "fwd" else 2)


def test_splash_attention_compiles_fwd_bwd(one_chip):
    from ray_tpu.ops.splash_attention import splash_mha

    def loss(q, k, v):
        return splash_mha(q, k, v, causal=True,
                          interpret=False).astype(jnp.float32).sum()

    _, text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                       *_qkv(one_chip, 4, 2048, 16, 8, 128))   # llama-1b heads
    assert KERNEL in text



CELL_SLOTS, CELL_MAX_LEN = 33, 2048      # the mistral cell's stacked cache


def test_offset_flash_kernel_compiles_over_the_stack(one_chip):
    """A chunk's queries [1, 512, 32, 128] over a slot's 2,048 rows of the
    cell's stacked cache, where they lie: a head is a block of 128 lanes of a
    row, so no slab is sliced out and nothing is transposed beside it."""
    from ray_tpu.ops import flash_attention as fa
    S = shapes_on(one_chip)
    stack = S((14, CELL_SLOTS, CELL_MAX_LEN, 1024))
    i32 = S((), jnp.int32)
    compiled, text = _compile(
        lambda q, k, v, layer, slot, start: fa.flash_attention_rows(
            q, k, v, layer, slot, start, CELL_MAX_LEN, 8, use_kernel=True,
            interpret=False),
        S((1, 512, 32, 128)), stack, stack, i32, i32, i32)
    assert text.count(KERNEL) == 1 and fa.KERNEL_FLASH_ROWS in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6
    assert not _slab_ops(text, 1, CELL_MAX_LEN, 1024)


@pytest.mark.parametrize("shape", [
    pytest.param((14, CELL_SLOTS, CELL_MAX_LEN, 32, 8), id="mistral-cell"),
    pytest.param((3, 25, 4096, 30, 30), id="hybrid-cell"),
])
def test_decode_attn_kernel_compiles_in_place(one_chip, shape):
    """The kernel alone at both cells' sizes (2 KB rows: 512 positions a
    block; 7.5 KB rows: 256): it reads the stack it is given, nothing is
    laid out anew beside it."""
    from ray_tpu.ops import decode_attention as da
    layers, slots, max_len, nh, nkv = shape
    S = shapes_on(one_chip)
    stack = S((layers, slots, max_len, nkv * 128), jnp.bfloat16)
    compiled, text = _compile(
        lambda q, k, v, i, n: da.decode_attn(q, k, v, i, n, nkv,
                                             use_kernel=True,
                                             interpret=False),
        S((slots, nh, 128), jnp.bfloat16), stack, stack, S((), jnp.int32),
        S((slots,), jnp.int32))
    assert KERNEL in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6


# ------------------------- the kinds' own kernels at their published sizes
# (what to compile, its arguments, what is donated, the kernel calls in the
# text (None: at least one), the temporaries' limit in bytes, stacks that may
# not be copied)

def _delta_rule(S, kernel, ops, name, nh, dk, dv, chunk, layers, slots,
                per_channel):
    """A delta-rule mixer's two kernels: the chunked forward over ``chunk``
    (rows, positions) and a step of ``slots`` slots on a stack of ``layers``
    as the cache holds it (``gated_delta.packed_shape``: the heads' own
    shape where they are 128 lanes wide), in place (the donated stack is the
    output, nothing beside it)."""
    from ray_tpu.ops.gated_delta import packed_shape
    f32 = jnp.float32

    def decay(*lead):
        return S(lead + ((dk,) if per_channel else ()), f32)

    if kernel == "chunk_fwd":
        b, t = chunk
        return (lambda *a: getattr(ops, name + "_chunk_fwd")(
            *a, use_kernel=True, interpret=False),
                (S((b, t, nh, dk)), S((b, t, nh, dk)), S((b, t, nh, dv)),
                 decay(b, t, nh), S((b, t, nh), f32), S((b,), jnp.int32)),
                (), None, None, ())
    return (lambda *a: getattr(ops, name + "_recurrent_step")(
        *a, use_kernel=True, interpret=False),
            (S((layers, slots) + packed_shape(nh, dk, dv), f32),
             S((), jnp.int32), S((slots, nh, dk)), S((slots, nh, dk)),
             S((slots, nh, dv)), decay(slots, nh), S((slots, nh), f32)),
            (0,), None, 1e6, ())


def _gdn(S, kernel):
    """30 heads of 96 / 192: neither a multiple of the 128 lanes; the step's
    stack holds them two a tile, [9, 25, 15, 96, 384]."""
    from ray_tpu.ops import gated_delta
    return _delta_rule(S, kernel, gated_delta, "gdn", 30, 96, 192, (2, 2048),
                       9, 25, False)


def _kda(S, kernel):
    """64 heads of 128 / 128, the decay a [.., 128] float32 row a head: one
    head a tile, the stack [3, 65, 64, 128, 128] as it was."""
    from ray_tpu.ops import kda
    return _delta_rule(S, kernel, kda, "kda", 64, 128, 128, (1, 1024), 3, 65,
                       True)


def _ssd(S, kernel):
    """64 heads of 64 over 8 groups of 128; an expert of 2688 x 1856, whose
    width is no multiple of the 128 lanes: one block is the whole matrix."""
    from ray_tpu.ops import moe, ssd
    nh, p, g, n, f32 = 64, 64, 8, 128, jnp.float32
    if kernel == "chunk_fwd":
        b, t = 1, 2048
        return (lambda *a: ssd.ssd_chunk_fwd(*a, use_kernel=True,
                                             interpret=False),
                (S((b, t, nh, p)), S((b, t, nh), f32), S((nh,)),
                 S((b, t, g, n)), S((b, t, g, n)), S((nh,)),
                 S((b,), jnp.int32)), (), None, None, ())
    if kernel == "recurrent_step":
        slots = 65
        return (lambda *a: ssd.ssd_recurrent_step(*a, use_kernel=True,
                                                  interpret=False),
                (S((4, slots, nh, p, n), f32), S((), jnp.int32),
                 S((slots, nh, p)), S((slots, nh), f32), S((nh,)),
                 S((slots, g, n)), S((slots, g, n)), S((nh,))), (0,), None,
                1e6, ())
    held, h, em, tile = 64, 2688, 1856, 16
    rows = (64 * 6 + held * (tile - 1) + tile - 1) // tile * tile

    def up_down(x, w_up, w_out, layer, tile_expert, tiles):
        plan = dict(layer=layer, tile_expert=tile_expert, tiles=tiles,
                    tile=tile, use_kernel=True, interpret=False)
        act = moe.moe_gmm(x, (w_up,), activation="relu2", transposed=True,
                          **plan)
        return moe.moe_gmm(act, (w_out,), **plan)

    return (up_down, (S((rows, h)), S((4, held, em, h)), S((4, held, em, h)),
                      S((), jnp.int32), S((rows // tile,), jnp.int32),
                      S((), jnp.int32)), (), 2, None, ())


def _granite(S, kernel):
    """64 state-space heads of 64 in ONE group of 128: four steps of 16 heads
    a chunk in the prefill kernel, each reading the group's B and C, and
    the decode kernel's planned block (the test after this one) on a stack
    of 36 layers in place; and attention heads
    of 64 lanes (32 over 8 K/V heads, rows of 512 lanes, two heads to a
    128-lane tile) through ``decode_attn`` and the flash forward at the
    published scale, 1 / 64."""
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops import ssd
    nh, p, g, n, f32, i32, slots = 64, 64, 1, 128, jnp.float32, jnp.int32, 65
    assert ssd._head_block(nh // g, ssd.CHUNK_HEADS_A_STEP) == 16
    if kernel == "chunk_fwd":
        b, t = 1, 4096
        return (lambda *a: ssd.ssd_chunk_fwd(*a, use_kernel=True,
                                             interpret=False),
                (S((b, t, nh, p)), S((b, t, nh), f32), S((nh,)),
                 S((b, t, g, n)), S((b, t, g, n)), S((nh,)),
                 S((b,), i32)), (), None, None, ())
    if kernel == "recurrent_step":
        return (lambda *a: ssd.ssd_recurrent_step(*a, use_kernel=True,
                                                  interpret=False),
                (S((36, slots, nh, p, n), f32), S((), i32),
                 S((slots, nh, p)), S((slots, nh), f32), S((nh,)),
                 S((slots, g, n)), S((slots, g, n)), S((nh,))), (0,), None,
                1e6, ("f32[36,65,64,64,128]",))
    if kernel == "decode_attn":
        stack = S((4, slots, 4096, 512))
        return (lambda q, k, v, i, live: da.decode_attn(
            q, k, v, i, live, 8, use_kernel=True, interpret=False,
            scale=1 / 64),
                (S((slots, 32, 64)), stack, stack, S((), i32),
                 S((slots,), i32)), (), 1, 16e6, ("bf16[4,65,4096,512]",))
    return (lambda q, k, v: fa.flash_attention(q, k, v, interpret=False,
                                               scale=1 / 64),
            (S((1, 4096, 32, 64)), S((1, 4096, 8, 64)),
             S((1, 4096, 8, 64))), (), 1, None, ())


def _moe_and_latent(S, kernel):
    """The grouped matmul over [layers, 64, 3584, 1024] stacks at a decode
    step's tiles of 16 rows and a prefill row's of 256, gated and plain; the
    latent kernel over 512-lane rows and 64 x 8,192 rotary keys.  Each reads
    its stack where it lies: nothing near a layer's size is temporary."""
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import moe
    i32, slots, max_len = jnp.int32, 33, 8192
    if kernel == "mla_decode_attn":
        return (lambda *a: da.mla_decode_attn(*a, 0.1, use_kernel=True,
                                              interpret=False),
                (S((slots, 32, 512)), S((slots, 32, 64)),
                 S((7, slots, max_len, 512)), S((7, slots, 64, max_len)),
                 S((), i32), S((slots,), i32)), (), None, 0.2e9,
                kinds.KINDS["xing4_0"].stacks)
    tokens = 33 if kernel.endswith("decode") else 8192
    tile = moe.tile_rows(tokens * 4, 64)
    assert tile == (16 if tokens == 33 else 256)
    rows = -(-(tokens * 4 + 64 * (tile - 1)) // tile) * tile
    return (lambda x, wg, wi, wo, layer, te, tiles: moe.moe_gmm(
        moe.moe_gmm(x, (wg, wi), layer, te, tiles, tile, use_kernel=True,
                    interpret=False),
        (wo,), layer, te, tiles, tile, use_kernel=True, interpret=False),
        (S((rows, 3584)), S((6, 64, 3584, 1024)), S((6, 64, 3584, 1024)),
         S((6, 64, 1024, 3584)), S((), i32), S((rows // tile,), i32),
         S((), i32)), (), 2, 0.2e9, ())


KINDS_KERNELS = [
    *((_gdn, "olmo_hybrid", k) for k in ("chunk_fwd", "recurrent_step")),
    *((_moe_and_latent, "xing4_0", k) for k in (
        "moe_gmm-decode", "moe_gmm-8192", "mla_decode_attn")),
    *((_kda, "solar_open2", k) for k in ("chunk_fwd", "recurrent_step")),
    *((_ssd, "nemotron_h", k) for k in ("chunk_fwd", "recurrent_step",
                                        "moe_gmm-relu2")),
    *((_granite, "granitemoehybrid", k) for k in (
        "chunk_fwd", "recurrent_step", "decode_attn", "flash_fwd")),
]


@pytest.mark.parametrize("build,name,kernel", KINDS_KERNELS,
                         ids=[f"{n}-{k}" for _, n, k in KINDS_KERNELS])
def test_the_kinds_kernels_compile_at_published_sizes(one_chip, build, name,
                                                      kernel):
    fn, args, donated, calls, temp, stacks = build(shapes_on(one_chip),
                                                   kernel)
    compiled, text = _compile(fn, *args, donate_argnums=donated)
    assert KERNEL in text and calls in (None, text.count(KERNEL))
    if temp:
        assert compiled.memory_analysis().temp_size_in_bytes < temp
    for stack in stacks:
        assert not copies_of(stack, text)


# -------------- the recurrent step's block plan at both cells' shapes

@pytest.mark.parametrize("layers,groups", [
    pytest.param(4, 8, id="nemotron_h"),
    pytest.param(36, 1, id="granitemoehybrid")])
def test_ssd_recurrent_step_compiles_under_its_vmem_in_place(
        one_chip, monkeypatch, layers, groups):
    """The decode kernel at the plan ``step_block`` makes from each cell's
    shapes, two whole slots a grid step over 65 (the last block holds one):
    the state's four buffers lie under ``STEP_STATE_VMEM``, Mosaic accepts
    the kernel under ``STEP_VMEM_LIMIT`` and refuses it under a limit that
    the blocks alone pass (so a plan that overflowed would be refused here
    and not at a cell's first step), and the stack is updated where it
    lies: aliased whole, nothing of a layer's size temporary, no copy."""
    from ray_tpu.ops import ssd
    slots, nh, p, n, f32 = 65, 64, 64, 128, jnp.float32
    sb, hb = ssd.step_block(slots, nh, nh // groups, p, n)
    assert (sb, hb) == (2, 64)
    blocks = 4 * sb * hb * ssd._head_bytes(p, n)
    assert blocks == 16 << 20 <= ssd.STEP_STATE_VMEM < ssd.STEP_VMEM_LIMIT
    S = shapes_on(one_chip)
    args = (S((layers, slots, nh, p, n), f32), S((), jnp.int32),
            S((slots, nh, p)), S((slots, nh), f32), S((nh,)),
            S((slots, groups, n)), S((slots, groups, n)), S((nh,)))

    def compile_step():     # a function of its own each time: a new trace
        return _compile(lambda *a: ssd.ssd_recurrent_step(
            *a, use_kernel=True, interpret=False), *args, donate_argnums=(0,))

    compiled, text = compile_step()
    mem = compiled.memory_analysis()
    assert text.count(KERNEL) == 1
    assert mem.alias_size_in_bytes == layers * slots * nh * p * n * 4
    assert mem.temp_size_in_bytes < 1e6
    assert not copies_of(f"f32[{layers},{slots},{nh},{p},{n}]", text)
    monkeypatch.setattr(ssd, "STEP_VMEM_LIMIT", blocks - (1 << 20))
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        compile_step()


# ------- the delta rule's packed state at the hybrid cell's shape (PR 57)

def test_gdn_recurrent_step_compiles_packed_under_its_vmem_in_place(
        one_chip, monkeypatch):
    """The hybrid's 30 heads of 96 x 192 lie two a tile, [9, 25, 15, 96,
    384]: by the compile the stack is its own 0.498e9 bytes, where the plain
    [9, 25, 30, 96, 192] is 0.664e9 on the chip (every head's 192 lanes in
    256), which is what ``cache_state_hbm_bytes`` reckons of each; the
    kernel takes a whole slot a grid step (``step_block``), Mosaic accepts
    it under ``STEP_VMEM_LIMIT`` and refuses it under a limit that the
    blocks alone pass, and the stack is updated where it lies.  The KDA
    kind's heads of 128 lanes pack one a tile: its stack and its case above
    are as they were."""
    from ray_tpu.ops import gated_delta as gd
    layers, slots, nh, dk, dv, f32 = 9, 25, 30, 96, 192, jnp.float32
    own = layers * slots * nh * dk * dv * 4
    assert gd.packed_shape(nh, dk, dv) == (15, 96, 384)
    assert gd.packed_shape(64, 128, 128) == (64, 128, 128)
    assert gd.step_block(slots, 15, dk, 384) == (1, 15)
    blocks = 4 * 15 * gd._tile_bytes(dk, 384)
    assert blocks == 4 * 15 * 96 * 384 * 4 <= gd.STEP_STATE_VMEM
    assert gd.STEP_STATE_VMEM < gd.STEP_VMEM_LIMIT
    S = shapes_on(one_chip)

    def compile_step(*state):   # a function of its own each time: a new trace
        return _compile(lambda *a: gd.gdn_recurrent_step(
            *a, use_kernel=True, interpret=False),
            S((layers, slots) + state, f32), S((), jnp.int32),
            S((slots, nh, dk)), S((slots, nh, dk)), S((slots, nh, dv)),
            S((slots, nh), f32), S((slots, nh), f32), donate_argnums=(0,))

    def stored(*state):         # the gauge's reckoning of that stack
        cache = {"state": jax.ShapeDtypeStruct((layers, slots) + state, f32)}
        return decode.cache_gauges(kinds.cell_cfg("olmo_hybrid"), cache)[
            "cache_state_hbm_bytes"]

    compiled, text = compile_step(15, 96, 384)
    mem = compiled.memory_analysis()
    assert text.count(KERNEL) == 1
    assert mem.alias_size_in_bytes == own == 497_664_000 == stored(15, 96, 384)
    assert mem.argument_size_in_bytes - own < 1e6
    assert mem.temp_size_in_bytes < 1e6
    assert not copies_of("f32[9,25,15,96,384]", text)
    plain = compile_step(nh, dk, dv)[0].memory_analysis()
    assert plain.alias_size_in_bytes == own * 256 // 192 == stored(nh, dk, dv)
    monkeypatch.setattr(gd, "STEP_VMEM_LIMIT", blocks - (1 << 20))
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        compile_step(15, 96, 384)


# ------------------- the grouped matmul's block plan at every cell's widths

@pytest.mark.parametrize("tile", [16, 128, 256])
@pytest.mark.parametrize("name", kinds.EXPERT_KINDS)
def test_moe_gmm_compiles_under_its_vmem_at_every_cells_widths(one_chip,
                                                               name, tile):
    """An expert's up and down calls at the smallest decode tile, at
    K-EXAONE's decode tile of 128 and at the largest prefill tile, with the
    block ``gmm_block`` plans from the shapes: a plan that overflows
    ``VMEM_LIMIT`` is refused here and not at a cell's first admit.  (Kimi-VL
    trains with gate and up apart: the gated call is the larger.)"""
    from ray_tpu.ops import moe
    h, m, held, relu2 = kinds.expert_shapes(name)
    S, rows = shapes_on(one_chip), 4 * tile
    plan = (S((), jnp.int32), S((4,), jnp.int32), S((), jnp.int32))

    def up_down(x, ups, w_out, layer, tile_expert, tiles):
        gmm = functools.partial(moe.moe_gmm, layer=layer, tiles=tiles,
                                tile_expert=tile_expert, tile=tile,
                                use_kernel=True, interpret=False)
        kw = dict(activation="relu2", transposed=True) if relu2 else {}
        return gmm(gmm(x, ups, **kw), (w_out,))

    ups = (S((2, held, m, h)),) if relu2 else (S((2, held, h, m)),) * 2
    compiled, text = _compile(up_down, S((rows, h)), ups, S((2, held, m, h)),
                              *plan)
    assert text.count(KERNEL) == 2
    # the stacks are read where they lie
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6
