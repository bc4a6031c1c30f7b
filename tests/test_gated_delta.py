"""The delta rule's state as the cache holds it (ops/gated_delta.py, PR 57):
``p`` neighbouring heads side by side along the lanes, the fewest that fill
whole tiles of 128 lanes, chosen from the state's shape; the decode kernel's
block planned from that shape; and the packed step against the plain one.
On the CPU (the kernel interpreted); numbers here are about results, never
speed.

Equality to the bit between two compiled programs holds on the CPU only where
every product and sum is exact: its compiler contracts a multiply and an add
into one rounding where it sees fit, a program at a time (on the chip the
packed kernel read the parent's bits on random data: ``PERF.md`` section 6,
PR 57).  So the step's arithmetic is held to the bit inside one program on
random data (``test_a_packed_tile_steps_as_its_heads_do_apart``), and the
kernel, its twin, the plain step and the recurrence to the bit on data whose
arithmetic is exact (``exact``: few-bit integers, keys and queries powers of
two, a decay of one or nought), and to rounding on random data."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import decode
from ray_tpu.ops import gated_delta as gd

F32 = jnp.float32
#: (heads, dk, dv): the hybrid's published heads; one head a tile (128
#: lanes); two and four a tile at toy sizes; an odd count of the hybrid's
#: heads, which stays plain (192 lanes the chip pads)
SHAPES = {"hybrid": (30, 96, 192), "whole-tiles": (4, 128, 128),
          "two-a-tile": (4, 8, 64), "four-a-tile": (8, 8, 32),
          "odd-heads": (3, 96, 192)}


@pytest.mark.parametrize("nh,dv,p", [
    (30, 192, 2), (64, 128, 1), (4, 64, 2), (3, 192, 1), (8, 32, 4),
    (4, 16, 1), (8, 96, 4), (6, 96, 1), (64, 256, 1)])
def test_heads_a_tile_follow_from_the_states_shape(nh, dv, p):
    """The fewest heads whose lanes fill whole tiles of 128, where that many
    divide the heads; else one, the plain layout."""
    assert gd.packed_heads(nh, dv) == p
    assert gd.packed_shape(nh, 96, dv) == (nh // p, 96, p * dv)
    assert p == 1 or (p * dv) % 128 == 0 and nh % p == 0
    assert all((j * dv) % 128 for j in range(1, p))     # and no fewer fill


@pytest.mark.parametrize("shape", list(SHAPES))
def test_unpack_inverts_pack_and_a_tiles_heads_lie_side_by_side(shape):
    nh, dk, dv = SHAPES[shape]
    p = gd.packed_heads(nh, dv)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, nh, dk, dv))
    packed = gd.pack_state(x)
    assert packed.shape == (2, 3) + gd.packed_shape(nh, dk, dv)
    assert packed.size == x.size
    np.testing.assert_array_equal(gd.unpack_state(packed, nh), x)
    for head in (0, nh // 2, nh - 1):
        i, j = divmod(head, p)
        np.testing.assert_array_equal(
            packed[1, 2, i, :, j * dv:(j + 1) * dv], x[1, 2, head])
    if p == 1:
        assert packed is x


@pytest.mark.parametrize("slots,tiles,dk,width,block,unrolled", [
    (25, 15, 96, 384, (1, 15), 5),       # the hybrid's: a slot of 2.1 MiB
    (65, 64, 128, 128, (1, 64), 8),      # 64 heads of 128 x 128: 4 MiB
    (5, 2, 8, 128, (5, 2), 2),           # a toy: every slot in one block
    (65, 8, 64, 256, (8, 8), 8),         # slots of 0.5 MiB: eight a step
    (4, 30, 256, 1024, (1, 3), 1),       # a slot of 30 MiB: tiles of 1 MiB
], ids=["hybrid", "one-head-a-tile", "toy", "small-slots", "large-slot"])
def test_a_grid_step_is_whole_slots_or_tiles_of_one_slot(
        slots, tiles, dk, width, block, unrolled):
    """What a grid step of the decode kernel moves of the packed state,
    planned from its shape and ``STEP_STATE_VMEM`` alone (``step_block``),
    and the tiles its body unrolls (``STEP_UNROLL_BYTES``)."""
    sb, tb = gd.step_block(slots, tiles, dk, width)
    tile = gd._tile_bytes(dk, width)
    assert (sb, tb) == block and tiles % tb == 0
    assert 4 * sb * tb * tile <= gd.STEP_STATE_VMEM < gd.STEP_VMEM_LIMIT
    tu = gd._head_group(tb, max(gd.STEP_UNROLL_BYTES // tile, 1))
    assert tu == unrolled and tb % tu == 0
    assert not hasattr(gd, "STEP_HEADS_A_STEP")


def test_a_tile_too_large_for_the_kernels_vmem_is_refused():
    with pytest.raises(ValueError, match="do not fit"):
        gd.step_block(2, 2, 2048, 1024)


# --------------------------------------------------- the step's arithmetic

def _draw(slots, nh, dk, dv, seed, exact=False):
    """(plain state [3, slots, H, dk, dv], q, k, v, g, beta) of a step;
    the last slot idle (g 0, beta 0).  ``exact``: every product and sum of
    the step is exact in float32 whatever its order (integers up to 8, keys
    and queries 0 or a power of two down to a quarter, beta a multiple of a
    half, a decay of one or nought: sums of 96 stay under 2^24 steps)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    if exact:
        def ints(key, shape, top=8):
            return jax.random.randint(key, shape, -top, top + 1).astype(F32)

        def pow2(key, shape):
            a, b = jax.random.split(key)
            return jnp.sign(ints(a, shape, 1)) * jnp.exp2(
                -jax.random.randint(b, shape, 0, 3).astype(F32))

        state = ints(ks[0], (3, slots, nh, dk, dv))
        q, k = pow2(ks[1], (slots, nh, dk)), pow2(ks[2], (slots, nh, dk))
        v = ints(ks[3], (slots, nh, dv))
        g = jnp.where(jax.random.bernoulli(ks[4], 0.5, (slots, nh)), 0.0,
                      -jnp.inf)
        beta = 0.5 * jax.random.randint(ks[5], (slots, nh), 0, 5).astype(F32)
    else:
        state = jax.random.normal(ks[0], (3, slots, nh, dk, dv))
        q = jax.random.normal(ks[1], (slots, nh, dk))
        k = jax.random.normal(ks[2], (slots, nh, dk))
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[3], (slots, nh, dv))
        g = -0.2 * jax.random.uniform(ks[4], (slots, nh))
        beta = 2.0 * jax.random.uniform(ks[5], (slots, nh))
    return (state, q, k, v, g.at[slots - 1].set(0.0),
            beta.at[slots - 1].set(0.0))


@pytest.mark.parametrize("shape", ["hybrid", "whole-tiles", "four-a-tile"])
def test_a_packed_tile_steps_as_its_heads_do_apart(shape):
    """``_step_lanes`` on a tile of ``p`` heads side by side against
    ``_step_tile`` on each of them, random data, one program: every element
    sees the plain step's operations in their order, so the results are
    equal to the bit."""
    nh, dk, dv = SHAPES[shape]
    p = gd.packed_heads(nh, dv)
    state, q, k, v, g, beta = _draw(2, p, dk, dv, seed=3)   # (slot 1 idle)
    alpha = jnp.exp(g)

    @jax.jit
    def both(h, q, k, v, alpha, beta):
        apart = [gd._step_tile(h[j], q[j:j + 1], k[j:j + 1], v[j:j + 1],
                               alpha[j], beta[j]) for j in range(p)]
        tile = gd._step_lanes(
            gd.pack_state(h)[0], [gd._col(q[j:j + 1]) for j in range(p)],
            [gd._col(k[j:j + 1]) for j in range(p)], v.reshape(1, p * dv),
            list(alpha), list(beta))
        return (jnp.concatenate([o for o, _ in apart], 1),
                jnp.concatenate([s for _, s in apart], 1)), tile

    (o_want, h_want), (o, h) = both(state[0, 0], q[0], k[0], v[0], alpha[0],
                                    beta[0])
    assert float(jnp.abs(h_want - gd.pack_state(state[0, 0])[0]).max()) > 0.01
    np.testing.assert_array_equal(o, o_want)
    np.testing.assert_array_equal(h, h_want)


@pytest.mark.parametrize("exact", [True, False], ids=["exact", "random"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_packed_kernel_and_twin_equal_the_plain_step_and_the_recurrence(
        shape, exact):
    """The interpreted kernel and the twin on the packed stack, through
    ``unpack_state``, against ``_step_tile`` ``vmap``ped over the plain
    state's heads (today's step) and ``gdn_recurrence``: to the bit where
    the arithmetic is exact, to rounding on random data; the other layers
    and the idle slot (g 0, beta 0) are left to the bit."""
    nh, dk, dv = SHAPES[shape]
    slots = 3
    plain, q, k, v, g, beta = _draw(slots, nh, dk, dv, seed=11, exact=exact)
    packed, li = gd.pack_state(plain), jnp.int32(1)
    o_plain, h_plain = jax.jit(jax.vmap(jax.vmap(gd._step_tile)))(
        plain[1], q[:, :, None], k[:, :, None], v[:, :, None], jnp.exp(g),
        beta)
    o_rec, h_rec = gd.gdn_recurrence(q[:, None], k[:, None], v[:, None],
                                     g[:, None], beta[:, None], plain[1])
    assert float(jnp.abs(h_plain[:-1] - plain[1, :-1]).max()) > 0.01
    same = np.testing.assert_array_equal if exact else (
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-6))
    same(h_plain, h_rec)
    same(o_plain[:, :, 0], o_rec[:, 0])
    for step in (jax.jit(gd.gdn_recurrent_step_jnp),
                 jax.jit(lambda *a: gd.gdn_recurrent_step(*a,
                                                          interpret=True))):
        new, o = step(packed, li, q, k, v, g, beta)
        assert new.shape == packed.shape and o.shape == v.shape
        got = gd.unpack_state(new, nh)
        same(got[1], h_plain)
        same(o, o_plain[:, :, 0])
        np.testing.assert_array_equal(got[0], plain[0])
        np.testing.assert_array_equal(got[2], plain[2])
        np.testing.assert_array_equal(got[1, -1], plain[1, -1])


@pytest.mark.parametrize("fit", [2, 1, 0.4], ids=["two-slots", "one-slot",
                                                  "tiles-of-one"])
def test_every_block_plan_steps_the_same(fit, monkeypatch):
    """Blocks of two slots (the last holds one), of one, and of some of one
    slot's tiles, chunks of two tiles unrolled: the plan moves the block, not
    the result."""
    nh, dk, dv = 20, 8, 64                    # ten tiles of two heads
    plain, q, k, v, g, beta = _draw(5, nh, dk, dv, seed=5, exact=True)
    tiles, _, width = gd.packed_shape(nh, dk, dv)
    tile = gd._tile_bytes(dk, width)
    want, o_want = gd.gdn_recurrent_step_jnp(gd.pack_state(plain),
                                             jnp.int32(2), q, k, v, g, beta)
    monkeypatch.setattr(gd, "STEP_STATE_VMEM", int(fit * 4 * tiles * tile))
    monkeypatch.setattr(gd, "STEP_UNROLL_BYTES", 2 * tile)
    assert gd.step_block(5, tiles, dk, width) == (
        (fit, tiles) if fit >= 1 else (1, 2))
    new, o = gd.gdn_recurrent_step(gd.pack_state(plain), jnp.int32(2), q, k,
                                   v, g, beta, interpret=True)
    np.testing.assert_array_equal(new, want)
    np.testing.assert_array_equal(o, o_want)


# ------------------------------------ the packed state in the served model

@pytest.fixture(scope="module")
def packed_tiny():
    """The tiny hybrid with value heads of 64 lanes: two a tile."""
    kind = kinds.load("olmo_hybrid")
    doc = dict(kinds.doc("olmo_hybrid"), linear_value_head_dim=64)
    cfg = kind.program_config(doc)
    assert gd.packed_heads(cfg.linear_num_heads, cfg.linear_value_dim) == 2
    return kind, doc, cfg, kinds.init(kind.init_params, cfg, jnp.float32, 3)


def test_a_prefill_packs_the_state_its_decode_steps_read(packed_tiny):
    """A row of 37 (no whole chunk of 64) admitted into slot 2, then twelve
    decode steps, against the reference's one forward over the row: the
    state the chunked kernel returns plain is packed at ``state_write`` and
    stepped packed."""
    kind, doc, cfg, params = packed_tiny
    run = kinds.programs(cfg)
    toks = np.random.default_rng(7).integers(1, 256, size=49).astype(np.int32)
    n, steps = 37, 12
    cache = decode.init_kv_cache(cfg, 4, 128, jnp.float32)
    assert cache["state"].shape == (6, 4, 2, 8, 128)
    cache, lg = run.prefill(params, cache, kinds.padded([toks[:n]], 64),
                            np.array([n], np.int32), np.array([2], np.int32))
    got = [np.asarray(lg[0])]
    for t in toks[n:n + steps - 1]:
        cache, lg = run.step(params, cache,
                             np.array([0, 0, t, 0], np.int32),
                             np.array([False, False, True, False]))
        got.append(np.asarray(lg[2]))
    at = jnp.arange(n - 1, n + steps - 1)
    want = np.asarray(jax.jit(lambda p, t: kind.logits(p, t, doc, at))(
        params, jnp.asarray(toks[:n + steps - 1])))
    assert want.std() > 0.5
    np.testing.assert_allclose(np.stack(got), want, atol=5e-4)
    assert cache["state"].shape == (6, 4, 2, 8, 128)
    assert not np.asarray(cache["state"][:, [0, 1, 3]]).any()
    assert float(jnp.abs(cache["state"][:, 2]).max()) > 0.01


@pytest.mark.parametrize("name,changes,own,stored", [
    # heads of 16 lanes, four of them: eight would fill a tile, so the state
    # stays plain and the chip pads every head's 16 lanes to 128
    ("olmo_hybrid", {}, 4 * 8 * 16, 4 * 8 * 128),
    ("olmo_hybrid", {"linear_value_head_dim": 64}, 4 * 8 * 64, 2 * 8 * 128),
    ("solar_open2", {}, 4 * 16 * 16, 4 * 16 * 128),
], ids=["hybrid-tiny", "hybrid-two-a-tile", "kda-tiny"])
def test_the_gauges_count_the_state_as_the_chip_stores_it(name, changes, own,
                                                           stored):
    """``cache_state_hbm_bytes``: the per-sequence state with every array's
    minor dimension in whole tiles of 128 lanes; equal to
    ``cache_state_bytes`` where no lane holds nothing."""
    kind = kinds.load(name)
    cfg = kind.program_config(dict(kinds.doc(name), **changes))
    slots = 5
    cache = jax.eval_shape(lambda: decode.init_kv_cache(cfg, slots, 64,
                                                        jnp.float32))
    gauges = decode.cache_gauges(cfg, cache)
    rows, channels = cache["conv"].shape[2:]           # (the tail's too)
    assert gauges["cache_state_bytes"] == cfg.linear_layers * slots * (
        own + rows * channels) * 4
    assert gauges["cache_state_hbm_bytes"] == cfg.linear_layers * slots * (
        stored + rows * -(-channels // 128) * 128) * 4
