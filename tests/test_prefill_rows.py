"""The admit program walks counted rows (``decode.prefill``): one program a
bucket, of a fixed ``[prefill_batch, bucket]`` shape, whose loop over the
rows takes its trip count from ``real_mask``.  On all three cache trees
(rows, rows beside a recurrent state, pages), tiny models in float32 on the
CPU: an admit of n rows is n admits of one; what no real row names is left
as it was, byte for byte; and a row's first token is what a batch that
computes every padded row gives, at any temperature.  Results, never
speed."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, paged_decode, transformer
from ray_tpu.models.config import TransformerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = os.path.join(REPO, "benchmark", "models", "olmo_hybrid.py")

SLOTS, SCRATCH, MAX_LEN, BUCKET, BATCH = 7, 6, 64, 32, 4
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
        self.admit = jax.jit(lambda p, c, st, *a: decode.prefill_admit(
            p, c, st, *a[:7], self.cfg, 0, jnp.float32, *a[7:]))

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


@pytest.fixture(scope="module", params=["dense", "hybrid", "paged"])
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
    with_count, lg_count = decode.prefill(
        tree.params, cache, toks, lengths, slots, tree.cfg, jnp.float32,
        rows=jnp.int32(BATCH))
    without, lg = decode.prefill(tree.params, cache, toks, lengths, slots,
                                 tree.cfg, jnp.float32)
    for name, a in without.items():
        np.testing.assert_array_equal(a, with_count[name], err_msg=name)
    np.testing.assert_array_equal(lg, lg_count)
    for r in range(BATCH):
        np.testing.assert_allclose(lg[r], tree.reference(PROMPTS[r]),
                                   atol=5e-4)
