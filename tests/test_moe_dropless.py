"""The dropless expert layer (ops/moe.py ``moe_dropless``): the grouped
matmul kernel interpreted against its ``ragged_dot`` twin and against a loop
over the experts one at a time, the shares of a layer divided over holders
of its experts adding up to the whole, and what makes it dropless: a token's
output does not depend on its batch.  Float32, tiny sizes, on the CPU:
numbers here are about results, never speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.ops import moe

H, M, E, K, L = 128, 64, 8, 2, 3
SCALING = 2.0


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.PRNGKey(11), 8)

    def w(k, *shape):
        return jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5

    small = {"router": w(ks[0], H, E), "bias": jnp.zeros((E,)),
             "shared_gate": w(ks[1], H, M), "shared_in": w(ks[2], H, M),
             "shared_out": w(ks[3], M, H)}
    stacks = {"w_gate": w(ks[4], L, E, H, M), "w_in": w(ks[5], L, E, H, M),
              "w_out": w(ks[6], L, E, M, H)}
    return small, stacks


def tokens(t, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, H), jnp.float32)


def expert_loop(x, small, stacks, layer, live=None, shared=True):
    """Every expert on every token, times its gate: the plain form."""
    idx, gates = moe.route_sigmoid(x, small["router"], small["bias"], K,
                                   SCALING)
    if live is not None:
        gates = gates * live[:, None]
    out = jnp.zeros_like(x)
    for e in range(E):
        y = (jax.nn.silu(x @ stacks["w_gate"][layer, e])
             * (x @ stacks["w_in"][layer, e])) @ stacks["w_out"][layer, e]
        out = out + ((idx == e) * gates).sum(-1)[:, None] * y
    if shared:
        out = out + (jax.nn.silu(x @ small["shared_gate"])
                     * (x @ small["shared_in"])) @ small["shared_out"]
    return out


def dropless(x, small, stacks, layer, **kw):
    return moe.moe_dropless(x, small, stacks, layer, experts_per_token=K,
                            scaling=SCALING, **kw)


@pytest.mark.parametrize("t,dead", [(1, 0), (5, 2), (33, 0), (150, 20)])
@pytest.mark.parametrize("path", ["kernel", "twin"])
def test_grouped_matmul_equals_the_expert_loop(layer, t, dead, path):
    """A decode step's few rows an expert (tiles of 16) to a prefill row's
    dozens (tiles of 64), with tokens that are routed nowhere; the kernel
    interpreted and its ``ragged_dot`` twin.  5e-6 on outputs of order 1 is
    float32 rounding of sums in another order."""
    small, stacks = layer
    x = tokens(t, seed=t)
    live = jnp.arange(t) < t - dead
    kw = (dict(interpret=True) if path == "kernel"
          else dict(use_kernel=False))
    out, counts, chosen, _ = jax.jit(lambda x, l: dropless(
        x, small, stacks, l, live=live, **kw))(x, jnp.int32(1))
    np.testing.assert_allclose(out, expert_loop(x, small, stacks, 1, live),
                               atol=5e-6)
    assert int(counts[0]) == (t - dead) * K
    assert 0 < int(counts[1]) <= min(E, (t - dead) * K)
    # the choice it reports is the router's, for dead tokens too
    np.testing.assert_array_equal(chosen, moe.route_sigmoid(
        x, small["router"], small["bias"], K, SCALING)[0])


@pytest.mark.parametrize("tile", [16, 128, 256])
@pytest.mark.parametrize("call", ["up", "down"])
@pytest.mark.parametrize("name", kinds.EXPERT_KINDS)
def test_the_weight_block_is_planned_from_the_shapes(name, call, tile):
    """bf16 at every tile a cell runs: the block fits the budget, is the
    whole matrix wherever that fits, else the fewest equal strips of whole
    lanes that do, and tiles the matrix exactly."""
    h, m, _, relu2 = kinds.expert_shapes(name)
    kdim, n, matrices = (h, m, 1 if relu2 else 2) if call == "up" else (
        m, h, 1)
    bn = moe.gmm_block(kdim, n, matrices, tile, 2)
    fits = lambda b: moe._gmm_vmem(  # noqa: E731
        kdim, b, matrices, tile, 2) <= moe.VMEM_LIMIT
    assert fits(bn) and n % bn == 0
    # what the budget's arithmetic must keep (PERF.md section 6, PR 52):
    # K-EXAONE's gate and up alone cannot be whole (2 x 25 MB x 2 buffers)
    split = name == "exaone_moe" and call == "up"
    assert (bn == n) == (not split) == fits(n)
    if split:
        strips = n // bn
        assert bn % moe.LANES == 0 and strips == 2
        assert not any(fits(n // s) for s in range(1, strips))


def test_a_block_that_cannot_fit_is_refused_with_the_reason(monkeypatch):
    monkeypatch.setattr(moe, "VMEM_LIMIT", 1 << 20)
    with pytest.raises(ValueError, match="fits 1 MiB of VMEM"):
        moe.gmm_block(4096, 1280, 2, 16, 2)


def _gmm_case(form, layer_at, live_rows, seed=0):
    """Operands of one ``moe_gmm`` call: 4 experts of [128, 256] in a stack
    of 3 layers, 40 tokens with 2 assignments each of which the first
    ``live_rows`` tokens count, tiles of 16; and the expert loop's result
    for the live tiles."""
    layers, experts, kdim, n, tile, t = 3, 4, 128, 256, 16, 40
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = ((layers, experts, n, kdim) if form == "relu2"
             else (layers, experts, kdim, n))
    weights = tuple(jax.random.normal(k, shape) * kdim ** -0.5
                    for k in ks[:2 if form == "gated" else 1])
    idx = jax.random.randint(ks[2], (t, 2), 0, experts)
    held = jnp.broadcast_to((jnp.arange(t) < live_rows)[:, None], idx.shape)
    _, source, tile_expert, tiles, _ = moe.sort_by_expert(idx, held, experts,
                                                          tile)
    xs = jnp.take(jax.random.normal(ks[3], (t, kdim)), source, axis=0,
                  mode="fill", fill_value=0)
    hi = jax.lax.Precision.HIGHEST
    want = []
    for i in range(int(tiles)):
        rows, e = xs[i * tile:(i + 1) * tile], int(tile_expert[i])
        ws = [w[layer_at, e].T if form == "relu2" else w[layer_at, e]
              for w in weights]
        out = jnp.dot(rows, ws[0], precision=hi)
        if form == "gated":
            out = jax.nn.silu(out) * jnp.dot(rows, ws[1], precision=hi)
        elif form == "relu2":
            out = moe.relu2(out)
        want.append(out)
    kw = (dict(activation="relu2", transposed=True) if form == "relu2"
          else {})
    return (xs, weights, layer_at, tile_expert, tiles, tile), kw, want


@pytest.mark.parametrize("live_rows", [40, 9, 0],
                         ids=["all-live", "dead-tiles", "no-live-tile"])
@pytest.mark.parametrize("layer_at", [0, 2])
@pytest.mark.parametrize("plan", ["whole", "split"])
@pytest.mark.parametrize("form", ["gated", "one", "relu2"])
def test_the_kernel_under_each_plan_equals_its_twin_and_the_expert_loop(
        monkeypatch, form, plan, layer_at, live_rows):
    """The forward kernel interpreted, fetching an expert's matrix whole or
    in two strips of 128 (a budget that fits no more), in its three forms
    (gated; one matrix; the squared ReLU over a stack stored transposed), on
    the first and the last layer of the stack, with every tile live, with
    tiles past the last live one and with none: the live rows are the
    twin's and the loop's over the experts one at a time, and a split block
    changes no digit (each output element is the same one ``dot``)."""
    args, kw, want = _gmm_case(form, layer_at, live_rows)
    xs, weights, _, tile_expert, tiles, tile = args
    whole = moe.moe_gmm(*args, interpret=True, **kw)
    kdim, n = xs.shape[1], whole.shape[1]
    if plan == "split":
        monkeypatch.setattr(moe, "VMEM_LIMIT", moe._gmm_vmem(
            kdim, n // 2, len(weights), tile, 4))
    assert moe.gmm_block(kdim, n, len(weights), tile, 4) == (
        n if plan == "whole" else n // 2)
    got = (moe.moe_gmm(*args, interpret=True, **kw) if plan == "split"
           else whole)
    live = int(tiles) * tile
    assert live == len(want) * tile and (live > 0) == (live_rows > 0)
    assert live < xs.shape[0]             # some tile is always past the last
    np.testing.assert_array_equal(got[:live], whole[:live])
    twin = moe.moe_gmm(*args, use_kernel=False, **kw)
    np.testing.assert_allclose(got[:live], twin[:live], atol=5e-6)
    if want:
        np.testing.assert_allclose(got[:live], jnp.concatenate(want),
                                   atol=5e-6)


def test_the_shares_add_up_to_the_whole_layer(layer):
    """Two holders of 4 experts each, the shared expert counted once: their
    parts of the result sum to the whole layer's (the model-configs guide's
    test of a chip's share)."""
    small, stacks = layer
    x = tokens(40, seed=3)
    parts, ran = [], 0
    for start, shared in ((0, True), (4, False)):
        held = jax.tree.map(lambda w: w[:, start:start + 4], stacks)
        out, counts, *_ = dropless(x, small, held, 2, expert_start=start,
                                  shared=shared, use_kernel=False)
        parts.append(out)
        ran += int(counts[0])
    assert ran == 40 * K
    np.testing.assert_allclose(parts[0] + parts[1],
                               expert_loop(x, small, stacks, 2), atol=5e-6)


def test_a_tokens_output_does_not_depend_on_its_batch(layer):
    """No capacity: the first 7 tokens alone, or with 93 others that crowd
    their experts, come out the same.  ``moe_mlp`` at its capacity factor
    drops tokens there, which is why it is not for serving."""
    small, stacks = layer
    x = tokens(100, seed=5)
    alone, *_ = dropless(x[:7], small, stacks, 0, use_kernel=False)
    crowded, *_ = dropless(x, small, stacks, 0, use_kernel=False)
    np.testing.assert_allclose(crowded[:7], alone, atol=5e-6)


def test_the_router_is_sigmoid_scores_with_a_selection_bias(layer):
    small, _ = layer
    x = tokens(50, seed=7)
    idx, gates = moe.route_sigmoid(x, small["router"], small["bias"], K,
                                   SCALING)
    np.testing.assert_allclose(gates.sum(-1), SCALING, rtol=1e-6)
    # the bias moves the choice and not the gates: a large bias on expert 3
    # puts it in every token's set, gated by its own score
    biased = small["bias"].at[3].set(10.0)
    idx_b, gates_b = moe.route_sigmoid(x, small["router"], biased, K, SCALING)
    assert bool((idx_b == 3).any(-1).all())
    scores = jax.nn.sigmoid(x @ small["router"])
    picked = jnp.take_along_axis(scores, idx_b, axis=-1)
    np.testing.assert_allclose(
        gates_b, picked / picked.sum(-1, keepdims=True) * SCALING, rtol=1e-5)


@pytest.mark.parametrize("tile", [16, 128])
def test_the_sorted_layout_is_whole_tiles_of_one_expert(tile):
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, E, size=(60, K)), jnp.int32)
    held = jnp.asarray(rng.random((60, K)) < 0.8)
    dest, source, tile_expert, tiles, sizes = moe.sort_by_expert(
        idx, held, E, tile)
    dest, source, tile_expert = map(np.asarray, (dest, source, tile_expert))
    assert len(source) % tile == 0 and int(sizes.sum()) == int(held.sum())
    for t in range(60):
        for j in range(K):
            if held[t, j]:
                assert source[dest[t, j]] == t
                assert tile_expert[dest[t, j] // tile] == idx[t, j]
            else:
                assert dest[t, j] == len(source)
    assert int(tiles) == sum(-(-int(s) // tile) for s in sizes)
    assert (source[int(tiles) * tile:] == 60).all()


def test_the_capacity_layer_says_what_it_is_not_for():
    assert "not for serving" in moe.moe_mlp.__doc__
    assert "not for serving" in moe.__doc__.replace("**", "")
