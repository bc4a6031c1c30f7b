"""The admit program walks counted rows (``decode.prefill``): one program a
bucket, of a fixed ``[prefill_batch, bucket]`` shape, whose loop over the
rows takes its trip count from ``real_mask``.  On all three cache trees
(rows, rows beside a recurrent state, pages), tiny models in float32 on the
CPU: an admit of n rows is n admits of one; what no real row names is left
as it was, byte for byte; and a row's first token is what a batch that
computes every padded row gives, at any temperature.  And counted chunks
(PR 37): a dense tree whose bucket is four chunks long walks a row in the
chunks its prompt fills, each written into the slot and attending over what
the slot holds by then ("chunked": the dense tree again with a chunk of 8
positions, so that its bucket of 32 is four); a ``start_pos`` on a dense
tree continues what an earlier call wrote.  And latent rows (PR 47: "latent",
the tiny latent + dropless + hyper-connected tree of ``tests/test_latent.py``
with the routers' choices recorded): the same chunks through the latent
kind's own mixer, a chunk as long as the experts ask (``EXPERT_TILE``, 2
here: 8 experts, 2 a token, so 8 positions, twice the shortest chunk of 4).
Results, never speed."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import decode, paged_decode, transformer
from ray_tpu.models.config import TransformerConfig

SLOTS, SCRATCH, MAX_LEN, BUCKET, BATCH = 7, 6, 64, 32, 4
CHUNK = BUCKET // 4                    # the "chunked" tree's; 19, 5, 32 and
#                                        11 tokens are 3, 1, 4 and 2 chunks
TILE = 2            # the "latent" tree's ``EXPERT_TILE``: its chunk is CHUNK
_prefill = decode.prefill
PAGE, MAX_PAGES, NUM_PAGES = 8, MAX_LEN // 8, 40
PROMPT_LENS = (19, 5, 32, 11)          # one of them fills its bucket
ADMIT_SLOTS = (4, 0, 5, 2)             # slots 1 and 3 are never admitted

DENSE = TransformerConfig(vocab_size=128, num_layers=3, hidden_size=64,
                          num_heads=4, num_kv_heads=2, mlp_size=128,
                          max_seq_len=96)
HYBRID_DOC = dict(
    model_type="olmo_hybrid", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=256,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})


class Tree:
    """A model, a cache tree already in use (every array holds something),
    its admit program, and the plain reference of its last-token logits."""

    def __init__(self, name):
        self.name, self.paged = name, name == "paged"
        # the shortest chunk is ``prefill``'s argument and the experts' tile
        # a constant; the admit program passes none, so the tree's are
        # slipped in while it is traced
        self.chunk = {"chunked": CHUNK, "latent": CHUNK // 2}.get(
            name, decode.PREFILL_CHUNK)
        self.tile = TILE if name == "latent" else decode.EXPERT_TILE
        self.rows = decode.LATENT if name == "latent" else ("k", "v")
        # the plain reference of a prompt's last-token logits, under jit (a
        # compile a length)
        if name == "hybrid":
            kind = kinds.load("olmo_hybrid")
            self.cfg = kind.program_config(HYBRID_DOC)
            self.params = kinds.init(kind.init_params, self.cfg, seed=3)
            last = lambda p, t: kind.logits(  # noqa: E731
                p, t, HYBRID_DOC, jnp.array([t.shape[0] - 1]))[0]
        elif name == "latent":
            kind, doc = kinds.load("xing4_0"), kinds.doc("xing4_0")
            self.cfg, self.params = kinds.tiny("xing4_0")
            last = lambda p, t: kind.logits(  # noqa: E731
                p, t, doc, jnp.array([t.shape[0] - 1]), follow=None)[0]
        else:
            self.cfg = DENSE
            self.params = kinds.init(transformer.init_params, DENSE)
            last = lambda p, t: transformer.apply(  # noqa: E731
                p, t[None], DENSE, compute_dtype=jnp.float32)[0][0, -1]
        last = jax.jit(last)
        self.reference = lambda toks: last(self.params,
                                           jnp.asarray(toks, jnp.int32))
        if self.paged:
            cache = paged_decode.init_paged_cache(
                self.cfg, NUM_PAGES, PAGE, SLOTS, MAX_PAGES, jnp.float32)
        else:
            cache = decode.init_kv_cache(self.cfg, SLOTS, MAX_LEN,
                                         jnp.float32,
                                         expert_choices=name == "latent")
        keys = jax.random.split(jax.random.PRNGKey(9), len(cache))
        self.cache = {
            n: (jax.random.normal(k, a.shape, a.dtype)
                if jnp.issubdtype(a.dtype, jnp.floating)
                else jnp.full_like(a, 3))
            for k, (n, a) in zip(keys, sorted(cache.items()))}
        self.state = decode.init_decode_state(SLOTS, jax.random.PRNGKey(1))
        # the engine's admit_fn: a paged admit brings two arrays more
        def admit(p, c, st, *a):
            with mock.patch.object(decode, "prefill", self.prefill):
                return decode.prefill_admit(p, c, st, *a[:7], self.cfg, 0,
                                            jnp.float32, *a[7:])

        self.admit = jax.jit(admit)

    def prefill(self, *args, chunk=None, **kw):
        """``decode.prefill`` with the tree's chunk, or ``chunk``."""
        with mock.patch.object(decode, "EXPERT_TILE", self.tile):
            return _prefill(*args, chunk=chunk or self.chunk, **kw)

    def arrays(self, rows, temperature=0.0):
        """What ``LLMEngine._admit_arrays`` builds for the requests ``rows``
        (indices into ``PROMPT_LENS`` / ``ADMIT_SLOTS``): real rows first,
        the rest padded onto the scratch slot."""
        n_pad = BATCH - len(rows)
        toks = np.zeros((BATCH, BUCKET), np.int32)
        for i, r in enumerate(rows):
            toks[i, :PROMPT_LENS[r]] = PROMPTS[r]
        out = [toks,
               np.array([PROMPT_LENS[r] for r in rows] + [1] * n_pad,
                        np.int32),
               np.array([ADMIT_SLOTS[r] for r in rows] + [SCRATCH] * n_pad,
                        np.int32),
               np.array([temperature] * len(rows) + [0.0] * n_pad,
                        np.float32),
               np.array([5] * len(rows) + [1] * n_pad, np.int32),
               np.full((BATCH,), -1, np.int32),
               np.array([True] * len(rows) + [False] * n_pad)]
        if self.paged:
            table = np.zeros((BATCH, MAX_PAGES), np.int32)
            for i, r in enumerate(rows):       # five pages of its own a row
                table[i, :5] = 1 + 5 * r + np.arange(5)
            out += [np.zeros((BATCH,), np.int32), table]
        return out


PROMPTS = [np.random.default_rng(n).integers(1, 128, size=n).astype(np.int32)
           for n in PROMPT_LENS]


_trees = {}


def _tree(name):
    if name not in _trees:
        _trees[name] = Tree(name)
    return _trees[name]


@pytest.fixture(scope="module", params=["dense", "hybrid", "paged",
                                        "chunked", "latent"])
def tree(request):
    return _tree(request.param)


def _untouched(tree, rows):
    """Per cache array, the part no row of ``rows`` may write: every slot
    but theirs (the scratch slot among them) or, of a page arena, every
    page but theirs and the null page 0, where a row dumps its padding."""
    theirs = [ADMIT_SLOTS[r] for r in rows]
    slots = [s for s in range(SLOTS) if s not in theirs]
    pages = [p for p in range(1, NUM_PAGES)
             if not any(5 * r < p <= 5 * r + 5 for r in rows)]
    where = {"length": lambda a: a[slots]}
    if tree.paged:
        where.update(k=lambda a: a[:, pages], v=lambda a: a[:, pages])
        where["block_table"] = lambda a: a[[s for s in slots if s != SCRATCH]]
    else:
        where.update(dict.fromkeys(
            ("k", "v", "state", "conv", *decode.LATENT, decode.CHOICES),
            lambda a: a[:, slots]))
    return where


@pytest.mark.parametrize("n", [1, 3, BATCH])
def test_an_admit_of_n_rows_is_n_admits_of_one(tree, n):
    rows = list(range(n))
    before = jax.tree.map(np.asarray, tree.cache)
    cache, state, first = jax.tree.map(np.asarray, tree.admit(
        tree.params, tree.cache, tree.state, *tree.arrays(rows)))
    one_cache, one_state, one_first = tree.cache, tree.state, []
    for r in rows:
        one_cache, one_state, f = tree.admit(tree.params, one_cache,
                                             one_state, *tree.arrays([r]))
        one_first.append(int(f[0]))
    one_state = jax.tree.map(np.asarray, one_state)
    assert sorted(cache) == sorted(tree.cache)
    for name, a in cache.items():
        # (a padding row zeroes the scratch slot's block table, as ever)
        keep = slice(SCRATCH) if name == "block_table" else slice(None)
        np.testing.assert_array_equal(a[keep], one_cache[name][keep],
                                      err_msg=name)
    assert first[:n].tolist() == one_first
    theirs = [ADMIT_SLOTS[r] for r in rows]
    for name in ("tokens", "active", "temps", "budget", "eos"):
        np.testing.assert_array_equal(state[name][theirs],
                                      one_state[name][theirs], err_msg=name)
    assert state["tokens"][theirs].tolist() == one_first
    assert state["active"][theirs].all() and not state["active"][SCRATCH]
    assert cache["length"][theirs].tolist() == [PROMPT_LENS[r] for r in rows]
    # the scratch slot, the slots nobody was admitted to and the slots of
    # the rows past the count: as they were, byte for byte
    for name, part in _untouched(tree, rows).items():
        if name in cache:
            np.testing.assert_array_equal(part(cache[name]),
                                          part(before[name]), err_msg=name)
    # and the rows it did walk changed theirs
    assert not np.array_equal(cache[tree.rows[0]], before[tree.rows[0]])


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "temperature-0.8"])
def test_first_tokens_are_those_of_a_batch_that_walks_every_row(
        tree, temperature):
    """Until PR 32 the program computed all ``BATCH`` rows at once, padded
    ones as prompts of one token 0, and sampled the ``[BATCH, V]`` logits
    with the state's key.  The same sampling of the plain reference's
    logits, one full forward a row, gives the first tokens the counted
    rows give: a row's draw depends on the key, its index and its own
    logits only."""
    rows = [0, 1, 2]
    arrays = tree.arrays(rows, temperature)
    _, state, first = tree.admit(tree.params, tree.cache, tree.state, *arrays)
    every_row = jnp.stack([tree.reference(PROMPTS[r]) for r in rows]
                          + [tree.reference(np.zeros((1,), np.int32))])
    want = decode.sample_per_slot(every_row, tree.state["key"],
                                  jnp.asarray(arrays[3]), 0)
    assert np.asarray(first)[:3].tolist() == np.asarray(want)[:3].tolist()
    if temperature:       # and it is a draw: not the greedy token everywhere
        assert (np.asarray(want)[:3]
                != np.asarray(every_row[:3].argmax(-1))).any()
    assert not np.array_equal(state["key"], tree.state["key"])


def test_no_count_walks_every_row(tree):
    """``prefill`` without ``rows`` (the benchmark's reference check, a
    caller with no padding) fills every row's slot."""
    toks, lengths, slots = tree.arrays([0, 1, 2, 3])[:3]
    cache = tree.cache
    if tree.paged:
        cache = dict(cache, block_table=cache["block_table"].at[slots].set(
            tree.arrays([0, 1, 2, 3])[-1]))
    with_count, lg_count = jax.jit(lambda *a, rows: tree.prefill(
        *a, tree.cfg, jnp.float32, rows=rows))(
            tree.params, cache, toks, lengths, slots, rows=jnp.int32(BATCH))
    without, lg = jax.jit(lambda *a: tree.prefill(
        *a, tree.cfg, jnp.float32))(tree.params, cache, toks, lengths, slots)
    for name, a in without.items():
        np.testing.assert_array_equal(a, with_count[name], err_msg=name)
    np.testing.assert_array_equal(lg, lg_count)
    for r in range(BATCH):
        np.testing.assert_allclose(lg[r], tree.reference(PROMPTS[r]),
                                   atol=5e-4)


# -------------------------- counted chunks (PR 37; latent rows, PR 47)

@pytest.fixture(scope="module", params=["dense", "latent"])
def alone(request):
    """The trees of rows alone: what a position left in its slot is all a
    later one needs of it."""
    return _tree(request.param)


def _experts(num_experts, experts_per_token):
    """A configuration with so many dropless experts, so many a token."""
    import dataclasses
    return dataclasses.replace(_tree("latent").cfg, num_experts=num_experts,
                               experts_per_token=experts_per_token)


XING = (64, 4)          # the long-context cell's experts: a chunk of 2,048


@pytest.mark.parametrize("kind,experts,widths", [
    ("dense", None, ((128, 128), (1024, 1024), (1536, 1536), (2048, 512),
                     (4096, 512), (2048 + 256, 2048 + 256))),
    # an expert is handed 128 rows of a chunk under uniform routing:
    # 128 x 64 / 4 positions, and of the cell's five buckets one has four
    ("latent", XING, ((512, 512), (1024, 1024), (2048, 2048), (4096, 4096),
                      (8192, 2048), (16384, 2048), (8192 + 512, 8192 + 512))),
    # 16 rows an expert of the shortest chunk already: it stands
    ("latent", (8, 2), ((1024, 1024), (2048, 512), (4096, 512))),
    # no multiple of the shortest chunk is skipped: 128 x 12 / 2 = 768 -> 1,024
    ("latent", (12, 2), ((2048, 2048), (4096, 1024), (8192, 1024))),
    ("dense", XING, ((4096, 4096), (8192, 2048))),
])
def test_which_rows_walk_chunks_is_read_off_shapes(kind, experts, widths):
    """A tree of rows alone, K/V or latent, and a bucket of at least four
    chunks, a chunk as long as the configuration's experts ask; every other
    tree and every shorter bucket walks whole rows."""
    assert (decode.PREFILL_CHUNK, decode.EXPERT_TILE) == (512, 128)
    tree = _tree(kind)
    cfg = _experts(*experts) if experts else tree.cfg
    for bucket, width in widths:
        assert decode.prefill_width(tree.cache, bucket, cfg) == width
    if not experts:
        assert decode.prefill_width(tree.cache, BUCKET, cfg, CHUNK) == CHUNK
        assert decode.prefill_width(tree.cache, BUCKET, cfg,
                                    CHUNK * 2) == BUCKET
    for leaf in ("block_table", "state"):
        assert decode.prefill_width(dict(tree.cache, **{leaf: None}),
                                    8192, cfg) == 8192


def test_the_tiny_latent_trees_chunk_follows_the_expert_rule():
    latent = _tree("latent")
    assert (latent.cfg.num_experts, latent.cfg.experts_per_token) == (8, 2)
    with mock.patch.object(decode, "EXPERT_TILE", TILE):
        assert decode.prefill_width(latent.cache, BUCKET, latent.cfg,
                                    latent.chunk) == CHUNK == 2 * latent.chunk
        assert decode.prefill_width(latent.cache, BUCKET // 2, latent.cfg,
                                    latent.chunk) == BUCKET // 2
    # with the tile the chip's, the tiny tree's bucket is one row
    assert decode.prefill_width(latent.cache, BUCKET, latent.cfg,
                                latent.chunk) == BUCKET


_row_programs = {}


def _one_row(tree, toks, slot, chunk, cache=None, start_pos=None):
    """``prefill`` of one row into ``slot`` of the tree's cache (one
    compilation a tree and chunk: a prompt's length is data)."""
    key = (tree.name, chunk, start_pos is None)
    if key not in _row_programs:
        _row_programs[key] = jax.jit(
            lambda cache, *a, **kw: tree.prefill(
                tree.params, cache, *a, tree.cfg, jnp.float32, chunk=chunk,
                **kw))
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :len(toks)] = toks
    kw = {} if start_pos is None else {"start_pos": start_pos}
    cache, lg = _row_programs[key](
        tree.cache if cache is None else cache, padded,
        np.array([len(toks)], np.int32), np.array([slot], np.int32), **kw)
    return jax.tree.map(np.asarray, cache), np.asarray(lg)[0]


def _positions(tree, name, a, slot, where=slice(None)):
    """Positions ``where`` of ``slot`` of a cache array, positions leading
    (rotary keys lie [layers, slots, R, max_len])."""
    a = a[:, slot]
    return (np.moveaxis(a, -1, 0) if name == "rope_key"
            else np.moveaxis(a, 1, 0))[where]


# lengths that end inside the first chunk, a middle one and the last, and
# exactly on a chunk's edge
@pytest.mark.parametrize("length", [1, 5, 8, 11, 16, 19, 24, 27, 32])
def test_a_chunked_row_is_the_whole_row(alone, length):
    toks = np.random.default_rng(100 + length).integers(
        1, 128, size=length).astype(np.int32)
    before = jax.tree.map(np.asarray, alone.cache)
    whole, lg_whole = _one_row(alone, toks, 3, decode.PREFILL_CHUNK)
    chunked, lg = _one_row(alone, toks, 3, alone.chunk
                           if alone.name == "latent" else CHUNK)
    np.testing.assert_allclose(lg, lg_whole, atol=5e-4)
    # (the latent kind's reference compiles anew for every length, seconds
    # each: two lengths; tests/test_latent.py holds the whole row to it)
    if alone.name == "dense" or length in (19, 32):
        np.testing.assert_allclose(lg, alone.reference(toks), atol=5e-4)
    assert lg.argmax() == lg_whole.argmax()
    walked = -(-length // CHUNK) * CHUNK
    others = [s for s in range(SLOTS) if s != 3]
    for name in alone.rows:
        # the prompt's rows are the whole row's; the chunks past its last
        # token were not walked, and their rows of the slot are as they were
        np.testing.assert_allclose(
            _positions(alone, name, chunked[name], 3, slice(length)),
            _positions(alone, name, whole[name], 3, slice(length)),
            atol=1e-5)
        np.testing.assert_array_equal(
            _positions(alone, name, chunked[name], 3, slice(walked, None)),
            _positions(alone, name, before[name], 3, slice(walked, None)))
        # a neighbour's rows: byte for byte
        np.testing.assert_array_equal(chunked[name][:, others],
                                      before[name][:, others])
    want = before["length"].copy()
    want[3] = length
    np.testing.assert_array_equal(chunked["length"], want)
    if decode.CHOICES in before:
        # every token's experts are the whole row's, a padded position of a
        # walked chunk is routed nowhere, and what was not walked is as it was
        said = chunked[decode.CHOICES]
        np.testing.assert_array_equal(said[:, 3, :length],
                                      whole[decode.CHOICES][:, 3, :length])
        assert said[:, 3, :length].min() >= 0
        assert (said[:, 3, length:walked] == -1).all()
        np.testing.assert_array_equal(
            said[:, 3, walked:], before[decode.CHOICES][:, 3, walked:])
        np.testing.assert_array_equal(said[:, others],
                                      before[decode.CHOICES][:, others])


@pytest.mark.parametrize("chunked", [True, False],
                         ids=["in-chunks", "in-one-pass"])
@pytest.mark.parametrize("held", [8, 13, 24])
def test_a_start_on_a_dense_tree_continues_a_row(alone, held, chunked):
    """An earlier call wrote the prompt's first ``held`` tokens into the
    slot; a call with ``start_pos`` walks the rest, in chunks or (a short
    bucket) in one pass, and leaves what one call over the whole prompt
    leaves: on K/V rows and on latent rows (whose prefix is expanded again
    from the slot, a start that is no whole number of chunks too)."""
    chunk = decode.PREFILL_CHUNK if not chunked else (
        alone.chunk if alone.name == "latent" else CHUNK)
    toks = np.random.default_rng(200 + held).integers(
        1, 128, size=29).astype(np.int32)
    whole, lg_whole = _one_row(alone, toks, 2, decode.PREFILL_CHUNK)
    first, _ = _one_row(alone, toks[:held], 2, chunk)
    assert first["length"][2] == held
    rest, lg = _one_row(
        alone, toks[held:], 2, chunk, cache=jax.tree.map(jnp.asarray, first),
        start_pos=np.array([held], np.int32))
    np.testing.assert_allclose(lg, lg_whole, atol=5e-4)
    assert lg.argmax() == lg_whole.argmax()
    assert rest["length"][2] == len(toks)
    others = [s for s in range(SLOTS) if s != 2]
    for name in alone.rows + ((decode.CHOICES,) if decode.CHOICES in rest
                              else ()):
        np.testing.assert_allclose(
            _positions(alone, name, rest[name], 2, slice(len(toks))),
            _positions(alone, name, whole[name], 2, slice(len(toks))),
            atol=1e-5)
        np.testing.assert_array_equal(rest[name][:, others],
                                      first[name][:, others])


def test_a_start_is_refused_where_the_slot_holds_no_rows_to_start_after():
    hybrid = _tree("hybrid")
    toks, lengths, slots = hybrid.arrays([0])[:3]
    with pytest.raises(ValueError, match="start_pos: a recurrent state"):
        decode.prefill(hybrid.params, hybrid.cache, toks, lengths, slots,
                       hybrid.cfg, jnp.float32,
                       start_pos=jnp.zeros((BATCH,), jnp.int32))


# ------------- the forward kernel with a query offset (interpret mode)

W, KV_LEN, NH, NKV, D = 128, 512, 4, 2, 128     # a chunk of 128 in a row of 4


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("start", [0, W, 3 * W], ids=["0", "C", "3C"])
@pytest.mark.parametrize("d_qk,d_v", [(D, D), (192, 128)],
                         ids=["side-by-side", "apart-192-128"])
def test_the_offset_kernel_is_attend_with_a_q_offset(start, dtype, atol,
                                                     d_qk, d_v):
    """``flash_attention_rows`` with its kernel interpreted against its
    twin, the plain ``attend(q_offset=start)`` over the slot's slab: W
    queries at ``start ..`` of slot 1 of layer 1, over rows that hold the
    prefix, the queries' own rows and, past them, anything.  A position's
    heads side by side in its row, as the stacked K/V cache has them, and a
    head's rows apart at a latent head's two widths, keys of 192 and values
    of 128 as they are."""
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import attend
    apart = d_qk != D
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(start), 3)
    q = jax.random.normal(kq, (1, W, NH, d_qk), dtype)
    k_all, v_all = (
        jax.random.normal(key, (2, 3, NKV, KV_LEN + W, d) if apart
                          else (2, 3, KV_LEN + W, NKV * d), dtype)
        for key, d in ((kk, d_qk), (kv, d_v)))
    # what lies past the queries' rows is never read into a result
    past = (1, 1, slice(None), slice(start + W, None)) if apart else (
        1, 1, slice(start + W, None))
    k_all, v_all = (a.at[past].set(1e3) for a in (k_all, v_all))
    before = dict(fa.INTERPRET_TRACES)
    got = jax.jit(lambda q, k, v, at: fa.flash_attention_rows(
        q, k, v, 1, 1, at, KV_LEN, NKV, interpret=True))(
            q, k_all, v_all, jnp.int32(start))
    assert fa.INTERPRET_TRACES["flash"] == before.get("flash", 0) + 1

    def slab(a):
        if apart:
            return a[1, 1, :, :KV_LEN].swapaxes(0, 1)[None]
        return a[1, 1, :KV_LEN].reshape(1, KV_LEN, NKV, -1)

    want = attend(q, slab(k_all), slab(v_all), causal=True, q_offset=start)
    assert got.shape == (1, W, NH * d_v) and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32).reshape(1, W, -1), atol=atol)
    # and the twin is what a call that may not take the kernel gets
    twin = fa.flash_attention_rows(q, k_all, v_all, 1, 1, jnp.int32(start),
                                   KV_LEN, NKV, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(twin, np.float32),
                                  np.asarray(want, np.float32).reshape(
                                      1, W, -1))


def test_the_offset_kernel_says_what_it_cannot_run():
    from ray_tpu.ops import flash_attention as fa
    assert fa.flash_rows_supported(512, 2048, 128) is None
    assert "lanes" in fa.flash_rows_supported(512, 2048, 64)
    # a head of 192 lanes is no whole block of a row; apart, it is a block
    assert "lanes" in fa.flash_rows_supported(512, 2048, 192)
    assert fa.flash_rows_supported(2048, 8192, 192, apart=True) is None
    assert "lanes" in fa.flash_rows_supported(512, 2048, 96, apart=True)
    assert "multiple" in fa.flash_rows_supported(512, 2048 + 8, 128)
    q = jnp.zeros((1, 8, 2, 16))
    rows = jnp.zeros((1, 1, 32, 32))
    with pytest.raises(ValueError, match="cannot run this shape"):
        fa.flash_attention_rows(q, rows, rows, 0, 0, 0, 32, 2,
                                use_kernel=True, interpret=True)
    with pytest.raises(ValueError, match="softcap"):
        fa.flash_attention_rows(jnp.zeros((1, 128, 2, 128)),
                                jnp.zeros((1, 1, 128, 256)),
                                jnp.zeros((1, 1, 128, 256)), 0, 0, 0, 128, 2,
                                logit_softcap=30.0, use_kernel=True,
                                interpret=True)
