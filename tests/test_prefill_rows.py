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
tree continues what an earlier call wrote.  Results, never speed."""

import functools
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, paged_decode, transformer
from ray_tpu.models.config import TransformerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = os.path.join(REPO, "benchmark", "models", "olmo_hybrid.py")

SLOTS, SCRATCH, MAX_LEN, BUCKET, BATCH = 7, 6, 64, 32, 4
CHUNK = BUCKET // 4                    # the "chunked" tree's; 19, 5, 32 and
#                                        11 tokens are 3, 1, 4 and 2 chunks
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
        # the chunk's length is ``prefill``'s argument; the admit program
        # passes none, so the tree's is slipped in while it is traced
        self.chunk = CHUNK if name == "chunked" else decode.PREFILL_CHUNK
        self.prefill = functools.partial(decode.prefill, chunk=self.chunk)
        if name == "hybrid":
            from benchmark.lib.manifest import load_model
            kind = load_model(KIND)
            self.cfg = kind.program_config(HYBRID_DOC)
            self.params = kind.init_params(jax.random.PRNGKey(3), self.cfg,
                                           jnp.float32)
            self.reference = lambda toks: kind.logits(
                self.params, toks, HYBRID_DOC, jnp.array([len(toks) - 1]))[0]
        else:
            self.cfg = DENSE
            self.params = transformer.init_params(
                jax.random.PRNGKey(0), DENSE, dtype=jnp.float32)
            self.reference = lambda toks: transformer.apply(
                self.params, jnp.asarray(toks)[None], DENSE,
                compute_dtype=jnp.float32)[0][0, -1]
        if self.paged:
            cache = paged_decode.init_paged_cache(
                self.cfg, NUM_PAGES, PAGE, SLOTS, MAX_PAGES, jnp.float32)
        else:
            cache = decode.init_kv_cache(self.cfg, SLOTS, MAX_LEN,
                                         jnp.float32)
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


@pytest.fixture(scope="module", params=["dense", "hybrid", "paged",
                                        "chunked"])
def tree(request):
    return Tree(request.param)


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
            ("k", "v", "state", "conv"), lambda a: a[:, slots]))
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
    assert not np.array_equal(cache["k"], before["k"])


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
    with_count, lg_count = tree.prefill(
        tree.params, cache, toks, lengths, slots, tree.cfg, jnp.float32,
        rows=jnp.int32(BATCH))
    without, lg = tree.prefill(tree.params, cache, toks, lengths, slots,
                               tree.cfg, jnp.float32)
    for name, a in without.items():
        np.testing.assert_array_equal(a, with_count[name], err_msg=name)
    np.testing.assert_array_equal(lg, lg_count)
    for r in range(BATCH):
        np.testing.assert_allclose(lg[r], tree.reference(PROMPTS[r]),
                                   atol=5e-4)


# ------------------------------------------------ counted chunks (PR 37)

@pytest.fixture(scope="module")
def dense():
    return Tree("dense")


def test_which_rows_walk_chunks_is_read_off_shapes(dense):
    """A dense K/V tree and a bucket of at least four chunks; every other
    tree and every shorter bucket walks whole rows."""
    assert decode.PREFILL_CHUNK == 512
    for bucket, width in ((128, 128), (1024, 1024), (1536, 1536),
                          (2048, 512), (4096, 512), (2048 + 256, 2048 + 256)):
        assert decode.prefill_width(dense.cache, bucket) == width
    assert decode.prefill_width(dense.cache, BUCKET, CHUNK) == CHUNK
    assert decode.prefill_width(dense.cache, BUCKET, CHUNK * 2) == BUCKET
    for leaf in ("block_table", "state", "latent"):
        assert decode.prefill_width(dict(dense.cache, **{leaf: None}),
                                    2048) == 2048


def _one_row(tree, toks, slot, chunk, bucket=BUCKET, **kw):
    """``prefill`` of one row into ``slot`` of the tree's cache."""
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(toks)] = toks
    cache, lg = decode.prefill(
        tree.params, kw.pop("cache", tree.cache), padded,
        np.array([len(toks)], np.int32), np.array([slot], np.int32),
        tree.cfg, jnp.float32, chunk=chunk, **kw)
    return jax.tree.map(np.asarray, cache), np.asarray(lg)[0]


# lengths that end inside the first chunk, a middle one and the last, and
# exactly on a chunk's edge
@pytest.mark.parametrize("length", [1, 5, 8, 11, 16, 19, 24, 27, 32])
def test_a_chunked_row_is_the_whole_row(dense, length):
    toks = np.random.default_rng(100 + length).integers(
        1, 128, size=length).astype(np.int32)
    before = jax.tree.map(np.asarray, dense.cache)
    whole, lg_whole = _one_row(dense, toks, 3, decode.PREFILL_CHUNK)
    chunked, lg = _one_row(dense, toks, 3, CHUNK)
    np.testing.assert_allclose(lg, lg_whole, atol=5e-4)
    np.testing.assert_allclose(lg, dense.reference(toks), atol=5e-4)
    assert lg.argmax() == lg_whole.argmax()
    walked = -(-length // CHUNK) * CHUNK
    for name in ("k", "v"):
        # the prompt's rows are the whole row's; the chunks past its last
        # token were not walked, and their rows of the slot are as they were
        np.testing.assert_allclose(chunked[name][:, 3, :length],
                                   whole[name][:, 3, :length], atol=1e-5)
        np.testing.assert_array_equal(chunked[name][:, 3, walked:],
                                      before[name][:, 3, walked:])
        # a neighbour's rows: byte for byte
        others = [s for s in range(SLOTS) if s != 3]
        np.testing.assert_array_equal(chunked[name][:, others],
                                      before[name][:, others])
    want = before["length"].copy()
    want[3] = length
    np.testing.assert_array_equal(chunked["length"], want)


@pytest.mark.parametrize("chunk", [CHUNK, decode.PREFILL_CHUNK],
                         ids=["in-chunks", "in-one-pass"])
@pytest.mark.parametrize("held", [8, 13, 24])
def test_a_start_on_a_dense_tree_continues_a_row(dense, held, chunk):
    """An earlier call wrote the prompt's first ``held`` tokens into the
    slot; a call with ``start_pos`` walks the rest, in chunks or (a short
    bucket) in one pass, and leaves what one call over the whole prompt
    leaves."""
    toks = np.random.default_rng(200 + held).integers(
        1, 128, size=29).astype(np.int32)
    whole, lg_whole = _one_row(dense, toks, 2, decode.PREFILL_CHUNK)
    first, _ = _one_row(dense, toks[:held], 2, chunk)
    assert first["length"][2] == held
    rest, lg = _one_row(
        dense, toks[held:], 2, chunk, cache=jax.tree.map(jnp.asarray, first),
        start_pos=np.array([held], np.int32))
    np.testing.assert_allclose(lg, lg_whole, atol=5e-4)
    assert lg.argmax() == lg_whole.argmax()
    assert rest["length"][2] == len(toks)
    others = [s for s in range(SLOTS) if s != 2]
    for name in ("k", "v"):
        np.testing.assert_allclose(rest[name][:, 2, :len(toks)],
                                   whole[name][:, 2, :len(toks)], atol=1e-5)
        np.testing.assert_array_equal(rest[name][:, others],
                                      first[name][:, others])


def test_a_start_is_refused_where_the_slot_holds_no_rows_to_start_after():
    hybrid = Tree("hybrid")
    toks, lengths, slots = hybrid.arrays([0])[:3]
    with pytest.raises(ValueError, match="start_pos"):
        decode.prefill(hybrid.params, hybrid.cache, toks, lengths, slots,
                       hybrid.cfg, jnp.float32,
                       start_pos=jnp.zeros((BATCH,), jnp.int32))


# ------------- the forward kernel with a query offset (interpret mode)

W, KV_LEN, NH, NKV, D = 128, 512, 4, 2, 128     # a chunk of 128 in a row of 4


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("start", [0, W, 3 * W], ids=["0", "C", "3C"])
def test_the_offset_kernel_is_attend_with_a_q_offset(start, dtype, atol):
    """``flash_attention_rows`` with its kernel interpreted against its
    twin, the plain ``attend(q_offset=start)`` over the slot's slab: W
    queries at ``start ..`` of slot 1 of layer 1, over rows that hold the
    prefix, the queries' own rows and, past them, anything."""
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import attend
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(start), 3)
    q = jax.random.normal(kq, (1, W, NH, D), dtype)
    k_all, v_all = (jax.random.normal(key, (2, 3, KV_LEN + W, NKV * D), dtype)
                    for key in (kk, kv))
    # what lies past the queries' rows is never read into a result
    k_all, v_all = (a.at[1, 1, start + W:].set(1e3) for a in (k_all, v_all))
    before = dict(fa.INTERPRET_TRACES)
    got = jax.jit(lambda q, k, v, at: fa.flash_attention_rows(
        q, k, v, 1, 1, at, KV_LEN, NKV, interpret=True))(
            q, k_all, v_all, jnp.int32(start))
    assert fa.INTERPRET_TRACES["flash"] == before.get("flash", 0) + 1
    slab = lambda a: a[1, 1, :KV_LEN].reshape(1, KV_LEN, NKV, D)  # noqa: E731
    want = attend(q, slab(k_all), slab(v_all), causal=True, q_offset=start)
    assert got.shape == (1, W, NH * D) and got.dtype == dtype
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
