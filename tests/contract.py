"""What every served kind of model passes, written once (a module, no
tests): a kind's file subclasses ``ServedKind`` (``OnlyServed`` where the
row has what the engine, the train step and the configuration refuse of it)
with its row of ``tests/kinds.py`` and the tests run there, in that file's
process, under ids that name the kind.  ``Shares`` is for a kind that serves
a share of its routed experts, ``DeltaRule`` for the kernels of a mixer that
runs the delta rule; ``walks`` and ``cases`` make the parametrised tests
whose cases are a file's, a subclass's or a row's own.

A kind's file keeps what is its own: the mathematics of its kernels against
its recurrence, the tests of its reference, its particular refusals.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kinds
from ray_tpu.models import decode, transformer
from ray_tpu.models.config import TransformerConfig


def cases(arg, of_class):
    """Parametrise a contract test over ``of_class(cls)``, tuples of (id,
    ...) that the subclass or its row has: ``conftest.pytest_generate_tests``
    reads the mark where it knows the class."""
    def mark(fn):
        fn.class_cases = (arg, of_class)
        return fn
    return mark


class ServedKind:
    row: kinds.Kind = None

    @property
    def kind(self):
        return kinds.load(self.row.name)

    @property
    def doc(self):
        return kinds.doc(self.row.name)

    @property
    def tiny(self):
        return kinds.tiny(self.row.name)

    # --------------------------------------- the engine's path == reference

    def test_prefill_then_decode_equals_the_reference(self):
        """Right-padded rows in one bucket, then decode steps through the
        cache with the other slots idle, against the reference's one forward
        over each row's tokens: float32 on both sides, the program under
        ``jit``.  The row's ``parity`` has the split and the tolerance."""
        p = self.row.parity
        cfg, params = self.tiny
        toks, got, cache = kinds.parity_run(self.row.name)
        for t, n, g in zip(toks, p["lens"], got):
            want = kinds.reference(self.row.name, params, t[:n + p["steps"]],
                                   n - 1, **p["ref_kw"])
            assert want.std() > 0.5
            np.testing.assert_allclose(g, want, atol=p["atol"])
        # the slots nobody used hold nothing, and every length is its row's
        idle = [s for s in range(p["n_slots"]) if s not in p["slots"]]
        for name in ("state", "conv"):
            if name in cache:
                assert not np.asarray(cache[name][:, idle]).any()
        lengths = np.zeros(p["n_slots"], int)
        lengths[p["slots"]] = np.add(p["lens"], p["steps"])
        assert cache["length"].tolist() == lengths.tolist()
        if p.get("choices"):
            # the record of the routers' choices: the live slot's tokens,
            # each at its position, by the prefill and by the step alike
            chosen = np.asarray(cache[decode.CHOICES])
            assert chosen.shape == (cfg.expert_layers, p["n_slots"],
                                    p["max_len"], cfg.experts_per_token)
            (slot,), (n,) = p["slots"], lengths[p["slots"]]
            assert chosen[:, slot, :n].min() >= 0
            assert (chosen[:, idle] == -1).all()
            assert (chosen[:, slot, n:] == -1).all()
            assert int(cache["moe_counts"][0]) > 0

    def test_engine_generates_the_references_greedy_tokens(self):
        """Through ``LLMEngine``'s three calls: the reference's greedy choice
        after each prefix of what the engine wrote is the engine's next
        token (one forward over the whole answer), and the engine's gauges
        are the row's."""
        e = self.row.engine
        cfg, params = self.tiny
        eng = kinds.engine(cfg, params, compute_dtype=jnp.float32, **e["kw"])
        rng = np.random.default_rng(e["seed"])
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in e["lens"]]
        reqs = [eng.submit(p, max_tokens=e["max_tokens"]) for p in prompts]
        outs = []
        for r in reqs:
            out = []
            while isinstance(item := r.out.get(timeout=120), int):
                out.append(item)
            assert not isinstance(item, BaseException), item
            outs.append(out)
        stats = {**eng.counters(), **eng.breakdown()}
        for prompt, out in zip(prompts, outs):
            assert len(out) == e["max_tokens"]
            seq = np.array(prompt + out, np.int32)
            ref = kinds.reference(self.row.name, params, seq[:-1],
                                  len(prompt) - 1, **self.row.parity["ref_kw"])
            if "min_gap" in e:                            # no near tie
                top2 = np.sort(ref, axis=-1)[:, -2:]
                assert (top2[:, 1] - top2[:, 0]).min() > e["min_gap"]
            assert out == ref.argmax(-1).tolist()
        assert {k: stats[k] for k in e["gauges"]} == e["gauges"]
        if "admitted" in e:
            assert stats["moe_assignments"] > 0
            assert stats["moe_experts_touched"] > 0
            assert stats["moe_assignments_prefill"] == e["admitted"]

    # --------------------------------------------------------- the refusals

    @cases("change,match", lambda cls: cls.row.kind_refusals)
    def test_the_kind_refuses_what_the_block_cannot_express(self, change,
                                                            match):
        self.kind.program_config(self.doc)
        with pytest.raises(ValueError, match=match):
            self.kind.program_config({**self.doc, **change})

    # ------------------------------- the counts of the cell's configuration

    def test_counts_of_the_cells_configuration(self):
        """``num_params`` is the program's tree to the parameter, the
        matrices a layer are the row's, and the cache the engine would hold
        for the cell's file (its slots and the scratch row) has the row's
        gauges."""
        c, kind = self.row.counts, self.kind
        doc = kinds.cell_doc(self.row.name)
        cfg = kinds.cell_cfg(self.row.name)
        tree = jax.eval_shape(lambda k: kind.init_params(k, cfg, jnp.bfloat16),
                              jax.random.PRNGKey(0))
        leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
        assert kind.num_params(doc) == c["num_params"]
        assert cfg.num_params() <= c["num_params"] <= leaves  # the matrices
        assert leaves - cfg.num_params() < 1e-3 * leaves
        assert c.get("matrices_alone") or leaves == c["num_params"]
        assert c["slots"] == doc["serve"]["num_slots"] + 1
        if "per" in c:
            assert kind.layer_matrix_params(doc) == c["per"]
        if "gauges" in c:
            cache = jax.eval_shape(lambda: kind.init_cache(
                cfg, c["slots"], doc["serve"]["max_len"], jnp.bfloat16))
            assert decode.cache_gauges(cfg, cache) == c["gauges"](kind, doc)


class OnlyServed(ServedKind):
    """A kind whose cache or wiring the engine's other modes, the train step
    and the configuration's other mechanisms refuse."""

    @cases("kw,match", lambda cls: cls.row.engine_refusals)
    def test_the_engine_refuses_what_the_kinds_cache_cannot_do(self, kw,
                                                               match):
        from ray_tpu.serve.llm import LLMEngine
        cfg, params = self.tiny
        with pytest.raises(ValueError, match=match) as e:
            LLMEngine(cfg, params=params, num_slots=2, max_len=32, **kw)
        assert all(n in str(e.value) for n in self.row.engine_refusal_names)

    @pytest.mark.parametrize("what", ["make_train_step", "apply_trunk"])
    def test_training_refuses_what_is_only_served(self, what):
        cfg, params = self.tiny
        with pytest.raises(NotImplementedError,
                           match=self.row.train_refusal):
            if what == "apply_trunk":
                transformer.apply_trunk(params, jnp.zeros((1, 8), jnp.int32),
                                        cfg)
            else:
                from ray_tpu.parallel import MeshSpec, make_optimizer, \
                    make_train_step
                mesh = MeshSpec(fsdp=2).build(jax.devices()[:2])
                make_train_step(cfg, mesh, make_optimizer(), None)

    @cases("kw,match", lambda cls: cls.row.config_refusals[1])
    def test_config_refuses_what_the_kind_cannot_wire(self, kw, match):
        base = self.row.config_refusals[0]
        base = dataclasses.asdict(self.tiny[0]) if base is None else base
        TransformerConfig(**base)
        with pytest.raises(ValueError, match=match):
            TransformerConfig(**{**base, **kw})


class Shares:
    """A kind that serves a share of its routed experts."""

    def test_the_shares_add_up_to_the_whole_layer(self):
        """The shares' routed parts plus the shared expert counted once
        equal the uncut reference's layer, in the reference and in the
        program (``decode._experts``) alike."""
        kind, s = self.kind, self.row.shares
        n, held = s["n"], s["held"]
        whole = self.doc
        whole[s.get("key", "n_routed_experts")] = n * held
        del whole["reduced"], whole["share"]
        cfg = kind.program_config(whole)
        params = kinds.init(kind.init_params, cfg, seed=5)
        # a layer's router and shared expert: under its kind's blocks, or
        # (group None) in a stack by layer of their own
        group, at = s["moe_at"]
        lp = jax.tree.map(lambda a: a[at], (
            params["blocks"][group] if group else params["blocks"])["moe"])
        stacks = params["blocks"]["experts"]
        x = jax.random.normal(jax.random.PRNGKey(6), (24, 64))
        docs = []
        for share in range(n):
            docs.append(self.doc)
            docs[-1]["share"]["expert_start"] = held * share

        # one program: the uncut layer, then each share's part by the
        # reference and by the program
        @jax.jit
        def layers(x, lp, stacks):
            with jax.default_matmul_precision("highest"):
                want, _ = kind.expert_layer(x, lp, stacks, 2, whole)
                routed, _ = kind.expert_layer(x, lp, stacks, 2, whole,
                                              shared=False)
                parts, program, chosen = [], [], []
                for share, doc in enumerate(docs):
                    part = jax.tree.map(
                        lambda a: a[:, held * share:held * (share + 1)],
                        stacks)
                    parts.append(kind.expert_layer(x, lp, part, 2, doc,
                                                   shared=False)[0])
                    out, (_, said) = decode._experts(
                        x[None], {"moe": lp}, kind.program_config(doc), None,
                        jnp.float32, 2, part)
                    program.append(out[0])
                    chosen.append(said)
            return want, want - routed, parts, program, chosen

        want, shared, parts, program, chosen = layers(x, lp, stacks)
        assert all(c.shape == (1, 24, cfg.experts_per_token) for c in chosen)
        assert float(jnp.abs(want).mean()) > 0.1
        np.testing.assert_allclose(sum(parts) + shared, want, atol=1e-5)
        np.testing.assert_allclose(sum(p - shared for p in program) + shared,
                                   want, atol=1e-4)
        # a share is a part, not the whole
        assert float(jnp.abs(parts[0] + shared - want).max()) > 0.05


class DeltaRule:
    """The delta rule's chunked form and its two Pallas kernels (interpreted)
    against the recurrence one token at a time: ``ops.<name>_recurrence``,
    ``_chunk_fwd_jnp``, ``_chunk_fwd``, ``_recurrent_step_jnp``,
    ``_recurrent_step``; ``per_channel``: a decay of its own for every
    channel; ``heads``: of the chunked, the ragged and the step case;
    ``kernel_sizes``: (id, t, dk, dv) of the chunk kernel's cases, ``low``
    their channel that forgets."""
    ops = name = None
    per_channel, heads, kernel_sizes, low = False, (4, 4, 6), (), None

    def fn(self, what, **kw):
        """``ops.<name><what>``; with keywords (``interpret=True``), the
        kernel under them as one jitted program."""
        fn = getattr(self.ops, self.name + what)
        return jax.jit(lambda *a: fn(*a, **kw)) if kw else fn

    def inputs(self, b, t, nh, dk, dv, seed, low=None):
        """q, k (l2-normalised), v, the log decay g (``low``: one channel's
        alpha 0.05 at every step) and beta up to 2."""
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q = jax.random.normal(ks[0], (b, t, nh, dk))
        k = jax.random.normal(ks[1], (b, t, nh, dk))
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (b, t, nh, dv))
        g = -0.2 * jax.random.uniform(
            ks[3], (b, t, nh, dk) if self.per_channel else (b, t, nh))
        if low is not None:
            g = g.at[..., low].set(jnp.log(0.05))
        beta = 2.0 * jax.random.uniform(ks[4], (b, t, nh))
        return q, k, v, g, beta

    @pytest.mark.parametrize("t", [64, 192, 37, 100, 129])
    def test_chunked_form_equals_the_recurrence(self, t):
        """Lengths that are and are not multiples of the chunk, beta up to
        2.  Float32 both sides; 2e-5 on outputs of order 1 is rounding of a
        few dozen float32 products a chunk."""
        args = self.inputs(2, t, self.heads[0], 8, 16, seed=t)
        o_ref, h_ref = self.fn("_recurrence")(*args)
        o, h = jax.jit(self.fn("_chunk_fwd_jnp"))(*args)
        np.testing.assert_allclose(o, o_ref, atol=2e-5)
        np.testing.assert_allclose(h, h_ref, atol=2e-5)

    def test_chunked_form_stops_each_row_at_its_length(self):
        args = self.inputs(3, 128, self.heads[1], 8, 16, seed=5)
        o, h = jax.jit(self.fn("_chunk_fwd_jnp"))(*args,
                                                  jnp.array([50, 128, 1]))
        for row, n in enumerate([50, 128, 1]):
            o_ref, h_ref = self.fn("_recurrence")(
                *(a[row:row + 1, :n] for a in args))
            np.testing.assert_allclose(o[row:row + 1, :n], o_ref, atol=2e-5)
            np.testing.assert_allclose(h[row:row + 1], h_ref, atol=2e-5)

    @cases("t,dk,dv", lambda cls: cls.kernel_sizes)
    def test_chunk_kernel_interpreted_equals_its_twin(self, t, dk, dv):
        args = self.inputs(2, t, 2, dk, dv, seed=7, low=self.low)
        lengths = jnp.array([t - 9, t])
        o_t, h_t = jax.jit(self.fn("_chunk_fwd_jnp"))(*args, lengths)
        o, h = self.fn("_chunk_fwd", interpret=True)(*args, lengths)
        np.testing.assert_allclose(o, o_t, atol=1e-5)
        np.testing.assert_allclose(h, h_t, atol=1e-5)

    def test_step_kernel_interpreted_equals_its_twin_and_touches_one_layer(
            self):
        layers, slots, nh, dk, dv = 3, 5, self.heads[2], 8, 16
        state = jax.random.normal(jax.random.PRNGKey(1),
                                  (layers, slots, nh, dk, dv))
        q, k, v, g, beta = (a[:, 0] for a in self.inputs(slots, 1, nh, dk,
                                                         dv, seed=2))
        g = g.at[3].set(0.0)
        beta = beta.at[3].set(0.0)                 # an inactive slot
        s_t, o_t = self.fn("_recurrent_step_jnp")(state, jnp.int32(1), q, k,
                                                  v, g, beta)
        s, o = self.fn("_recurrent_step", interpret=True)(
            state, jnp.int32(1), q, k, v, g, beta)
        np.testing.assert_allclose(o, o_t, atol=1e-6)
        np.testing.assert_allclose(s, s_t, atol=1e-6)
        np.testing.assert_array_equal(s[0], state[0])
        np.testing.assert_array_equal(s[2], state[2])
        np.testing.assert_array_equal(s[1, 3], state[1, 3])
        o_ref, h_ref = self.fn("_recurrence")(
            q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
            state[1])
        np.testing.assert_allclose(o, o_ref[:, 0], atol=1e-5)
        np.testing.assert_allclose(s[1], h_ref, atol=1e-5)


def walks(trees, base, after):
    """``test_every_tree_walks_the_same_layer_stack`` over a file's
    ``trees`` (TransformerConfig kwargs over ``base``, by name): each traces
    to one scan over its periods (three here) whose body holds no scan over
    layers; ``after(cfg, params, new_cache)`` is what the file asserts of the
    step's result besides."""
    @pytest.mark.parametrize("name", list(trees))
    def test_every_tree_walks_the_same_layer_stack(name):
        cfg = TransformerConfig(**{**base(name), **trees[name]})
        params = kinds.init(transformer.init_params, cfg)
        cache = decode.init_kv_cache(cfg, 2, 32, jnp.float32,
                                     expert_choices=cfg.moe_dropless)
        step = lambda p, c: decode.decode_step(  # noqa: E731
            p, c, jnp.ones((2,), jnp.int32), jnp.ones((2,), bool), cfg,
            jnp.float32)
        over_layers = [
            e for e in kinds._scans(jax.make_jaxpr(step)(params, cache).jaxpr)
            if e.params["length"] == 3]
        assert len(over_layers) == 1
        new, logits = jax.jit(step)(params, cache)
        assert bool(jnp.isfinite(logits).all())
        after(cfg, params, new)
    return test_every_tree_walks_the_same_layer_stack
