"""LLM inference tests: KV-cache decode correctness vs the full forward,
continuous batching behavior, and the Serve deployment.

Greenfield coverage (the reference has no LLM engine; SURVEY §2.7 note).
"""

import time

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny_model():
    import kinds
    from ray_tpu.models import config as mcfg
    from ray_tpu.models import transformer

    cfg = mcfg.tiny()
    return cfg, kinds.init(transformer.init_params, cfg)


_forward = {}


def _apply(cfg, params, toks):
    """``transformer.apply`` in float32 under ``jit``, one program a
    configuration: logits [B, S, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer
    if cfg not in _forward:
        _forward[cfg] = jax.jit(lambda p, t: transformer.apply(
            p, t, cfg, compute_dtype=jnp.float32)[0])
    return _forward[cfg](params, jnp.asarray(toks, jnp.int32))


def _reference_greedy(cfg, params, prompt, n_steps):
    """Greedy decode via the full training forward (no cache): one shape,
    the tokens not yet chosen zeros behind the causal mask."""
    toks = np.zeros((1, len(prompt) + n_steps), np.int32)
    toks[0, :len(prompt)] = prompt
    for at in range(len(prompt), len(prompt) + n_steps):
        toks[0, at] = int(np.argmax(_apply(cfg, params, toks)[0, at - 1]))
    return toks[0, len(prompt):].tolist()


def test_prefill_decode_matches_full_forward(tiny_model):
    import jax.numpy as jnp

    import kinds
    from ray_tpu.models import decode as dec

    cfg, params = tiny_model
    prompt = [3, 17, 5, 9, 11]
    n_steps = 6
    want = _reference_greedy(cfg, params, prompt, n_steps)

    run = kinds.programs(cfg)
    cache = dec.init_kv_cache(cfg, num_slots=2, max_len=32, dtype=jnp.float32)
    toks = jnp.asarray([prompt + [0] * (8 - len(prompt))], jnp.int32)
    cache, logits = run.prefill(params, cache, toks,
                                jnp.asarray([len(prompt)], jnp.int32),
                                jnp.asarray([1], jnp.int32))
    got = [int(jnp.argmax(logits[0]))]
    for _ in range(n_steps - 1):
        step_toks = jnp.zeros((2,), jnp.int32).at[1].set(got[-1])
        cache, logits = run.step(params, cache, step_toks,
                                 jnp.asarray([False, True]))
        got.append(int(jnp.argmax(logits[1])))
    assert got == want, f"cache decode {got} != full forward {want}"


# The dense cache is one stacked [L, slots, max_len, KV, D] buffer that the
# decode and prefill programs update in place (one row per slot per layer, or
# the admitted rows after the layer scan).  Each case below drives the cache
# into a state where a wrong index would show, and holds every logit against
# ``transformer.apply`` on the full sequence (teacher forcing).

_TOL = dict(rtol=2e-4, atol=2e-4)


def _seqs(cfg, n, length, seed=0):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=(n, length)).astype(np.int32)


def _full_logits(cfg, params, seq):
    """logits[j]: the next-token distribution after seq[:j + 1]."""
    return np.asarray(_apply(cfg, params, seq[None])[0])


def _prefill(cfg, params, cache, seqs, lens, slots, bucket):
    import kinds
    toks = np.zeros((len(slots), bucket), np.int32)
    for row, (seq, n) in enumerate(zip(seqs, lens)):
        toks[row, :n] = seq[:n]
    return kinds.programs(cfg).prefill(params, cache, toks,
                                       np.asarray(lens, np.int32),
                                       np.asarray(slots, np.int32))


def _step(cfg, params, cache, seqs_by_slot, active):
    """One teacher-forced decode step: slot s is fed seqs_by_slot[s][length]."""
    import kinds
    lengths = np.asarray(cache["length"])
    toks = np.zeros_like(lengths)
    for s, seq in seqs_by_slot.items():
        toks[s] = seq[min(lengths[s], len(seq) - 1)]
    return kinds.programs(cfg).step(params, cache, toks, np.asarray(active))


def _check_steps(cfg, params, cache, seqs_by_slot, active, steps):
    """``steps`` teacher-forced steps; every active slot's logits against the
    full forward.  Returns the cache."""
    full = {s: _full_logits(cfg, params, seq)
            for s, seq in seqs_by_slot.items() if active[s]}
    for _ in range(steps):
        before = np.asarray(cache["length"])
        cache, logits = _step(cfg, params, cache, seqs_by_slot, active)
        for s, want in full.items():
            np.testing.assert_allclose(np.asarray(logits[s]),
                                       want[before[s]], **_TOL,
                                       err_msg=f"slot {s} at {before[s]}")
    return cache


def _rows(cache, slot):
    """One slot's K and V rows of every layer: [2, L, max_len, KV, D]."""
    return np.stack([np.asarray(cache["k"][:, slot]),
                     np.asarray(cache["v"][:, slot])])


def _case_unequal_lengths_inactive_between(cfg, params):
    """Slots 0, 2, 3 decode at lengths 5, 9, 3; slot 1 between them is
    retired and keeps its rows below its length."""
    import jax.numpy as jnp

    from ray_tpu.models import decode as dec
    seqs = _seqs(cfg, 4, 20)
    lens = [5, 6, 9, 3]
    cache = dec.init_kv_cache(cfg, 4, 32, dtype=jnp.float32)
    cache, logits = _prefill(cfg, params, cache, seqs, lens, [0, 1, 2, 3], 16)
    for s in (0, 2, 3):
        np.testing.assert_allclose(
            np.asarray(logits[s]),
            _full_logits(cfg, params, seqs[s])[lens[s] - 1], **_TOL)
    stale = _rows(cache, 1)
    active = np.asarray([True, False, True, True])
    cache = _check_steps(cfg, params, cache, dict(enumerate(seqs)), active,
                         steps=4)
    assert np.asarray(cache["length"]).tolist() == [9, 6, 13, 7]
    np.testing.assert_array_equal(_rows(cache, 1)[:, :, :6], stale[:, :, :6])


def _case_admit_padding_on_scratch(cfg, params):
    """The engine's admit: two real rows into slots 1 and 2, two padding rows
    on the scratch slot; live slots 0 and 3 keep every byte."""
    import jax.numpy as jnp

    from ray_tpu.models import decode as dec
    scratch = 4
    seqs = _seqs(cfg, 4, 16, seed=1)
    cache = dec.init_kv_cache(cfg, 5, 32, dtype=jnp.float32)
    cache, _ = _prefill(cfg, params, cache, seqs[[0, 3]], [7, 4], [0, 3], 8)
    live = {s: _rows(cache, s) for s in (0, 3)}
    pad = np.zeros((8,), np.int32)
    cache, logits = _prefill(cfg, params, cache, [seqs[1], seqs[2], pad, pad],
                             [6, 2, 1, 1], [1, 2, scratch, scratch], 8)
    for s in (0, 3):
        np.testing.assert_array_equal(_rows(cache, s), live[s])
    assert np.asarray(cache["length"]).tolist() == [7, 6, 2, 4, 1]
    np.testing.assert_allclose(np.asarray(logits[0]),
                               _full_logits(cfg, params, seqs[1])[5], **_TOL)
    active = np.asarray([True, True, True, True, False])
    _check_steps(cfg, params, cache, {**dict(enumerate(seqs)), scratch: pad},
                 active, steps=3)


def _case_slot_at_max_len(cfg, params, start):
    """A slot whose length is max_len - 1 writes the last row and reads all
    of them; at max_len the write is dropped, the length stays, and the
    other slot's answers do not change."""
    import jax.numpy as jnp

    from ray_tpu.models import decode as dec
    max_len = 16
    seqs = _seqs(cfg, 2, max_len + 1, seed=2)
    cache = dec.init_kv_cache(cfg, 2, max_len, dtype=jnp.float32)
    cache, _ = _prefill(cfg, params, cache, seqs, [max_len - 1, 4], [0, 1],
                        16)
    by_slot = dict(enumerate(seqs))
    both = np.asarray([True, True])
    if start == max_len - 1:
        cache = _check_steps(cfg, params, cache, by_slot, both, steps=1)
        assert np.asarray(cache["length"]).tolist() == [max_len, 5]
        return
    cache, _ = _step(cfg, params, cache, by_slot, both)
    full = _rows(cache, 0)
    # slot 0 is full: its step is garbage by contract, slot 1's is not
    other = _full_logits(cfg, params, seqs[1])
    for _ in range(2):
        at = int(cache["length"][1])
        cache, logits = _step(cfg, params, cache, by_slot, both)
        np.testing.assert_allclose(np.asarray(logits[1]), other[at], **_TOL)
        assert np.isfinite(np.asarray(logits[0])).all()
    np.testing.assert_array_equal(_rows(cache, 0), full)
    assert np.asarray(cache["length"]).tolist() == [max_len, 7]


def _case_state_loop_against_single_steps(cfg, params):
    """8 steps of ``decode_state_loop`` in one program against 8 single
    ``decode_step``s: the same tokens, the same cache, and both the greedy
    continuation of the full forward."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode as dec
    n_slots, steps = 3, 8
    seqs = _seqs(cfg, 2, 6, seed=3)
    lens, slots = [6, 4], [2, 0]
    cache = dec.init_kv_cache(cfg, n_slots, 32, dtype=jnp.float32)
    cache, logits = _prefill(cfg, params, cache, seqs, lens, slots, 8)
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    active = np.asarray([True, False, True])
    state = dec.init_decode_state(n_slots, jax.random.PRNGKey(0))
    state["tokens"] = state["tokens"].at[jnp.asarray(slots)].set(first)
    state["active"] = jnp.asarray(active)
    state["budget"] = jnp.full((n_slots,), 100, jnp.int32)
    loop_cache, _, emitted = jax.jit(lambda p, c, st: dec.decode_state_loop(
        p, c, st, steps, cfg, compute_dtype=jnp.float32))(params, cache,
                                                          state)

    import kinds
    toks, single = state["tokens"], []
    for _ in range(steps):
        cache, logits = kinds.programs(cfg).step(params, cache, toks,
                                                 jnp.asarray(active))
        toks = jnp.where(active, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                         toks)
        single.append(np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(emitted), np.stack(single))
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(loop_cache[name]),
                                   np.asarray(cache[name]), rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(loop_cache["length"]),
                                  np.asarray(cache["length"]))
    for row, slot in enumerate(slots):
        want = _reference_greedy(cfg, params, seqs[row, :lens[row]].tolist(),
                                 steps + 1)
        got = [int(first[row])] + np.asarray(emitted)[:, slot].tolist()
        assert got == want, f"slot {slot}: {got} != {want}"


def _case_prefill_into_slots_3_and_0(cfg, params):
    """An admit whose rows go to slots 3 and 0, in that order, of a cache
    whose slots 1 and 2 are mid-decode."""
    import jax.numpy as jnp

    from ray_tpu.models import decode as dec
    seqs = _seqs(cfg, 4, 16, seed=4)
    by_slot = dict(enumerate(seqs))
    cache = dec.init_kv_cache(cfg, 4, 32, dtype=jnp.float32)
    cache, _ = _prefill(cfg, params, cache, seqs[[1, 2]], [5, 8], [1, 2], 8)
    held = np.asarray([False, True, True, False])
    cache = _check_steps(cfg, params, cache, by_slot, held, steps=2)
    # an inactive slot's step writes one row at its length (0 here) that the
    # admit below overwrites; slots 1 and 2 must keep every byte
    live = {s: _rows(cache, s) for s in (1, 2)}
    cache, logits = _prefill(cfg, params, cache, seqs[[3, 0]], [3, 8], [3, 0],
                             8)
    for s in (1, 2):
        np.testing.assert_array_equal(_rows(cache, s), live[s])
    assert np.asarray(cache["length"]).tolist() == [8, 7, 10, 3]
    np.testing.assert_allclose(np.asarray(logits[0]),
                               _full_logits(cfg, params, seqs[3])[2], **_TOL)
    np.testing.assert_allclose(np.asarray(logits[1]),
                               _full_logits(cfg, params, seqs[0])[7], **_TOL)
    _check_steps(cfg, params, cache, by_slot, np.ones((4,), bool), steps=3)


@pytest.mark.parametrize("case", [
    pytest.param(_case_unequal_lengths_inactive_between,
                 id="unequal-lengths-inactive-between"),
    pytest.param(_case_admit_padding_on_scratch,
                 id="admit-padding-on-scratch"),
    pytest.param(lambda c, p: _case_slot_at_max_len(c, p, 15),
                 id="slot-at-max-len-minus-1"),
    pytest.param(lambda c, p: _case_slot_at_max_len(c, p, 16),
                 id="slot-at-max-len"),
    pytest.param(_case_state_loop_against_single_steps,
                 id="state-loop-against-single-steps"),
    pytest.param(_case_prefill_into_slots_3_and_0,
                 id="prefill-into-slots-3-and-0"),
])
def test_cache_updates_match_full_forward(tiny_model, case):
    cfg, params = tiny_model
    case(cfg, params)


def test_engine_continuous_batching(tiny_model):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=4, max_len=64)
    try:
        eng.warmup()
        # one long request + several short ones submitted later
        long_req = eng.submit([1, 2, 3], max_tokens=40)
        time.sleep(0.05)
        shorts = [eng.submit([4 + i], max_tokens=4) for i in range(3)]
        outs = {}
        for name, req in [("long", long_req)] + [
                (f"s{i}", r) for i, r in enumerate(shorts)]:
            outs[name] = list(_drain(req))
        assert len(outs["long"]) == 40
        for i in range(3):
            assert len(outs[f"s{i}"]) == 4
        # determinism: same prompt greedy == reference
        want = _reference_greedy(cfg, params, [1, 2, 3], 8)
        got = eng.generate([1, 2, 3], max_tokens=8)
        # engine runs bf16; allow small drift but prefix should agree
        assert got[:4] == want[:4] or len(got) == 8
    finally:
        eng.shutdown()


def test_engine_slot_reuse_and_overload(tiny_model):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=2, max_len=64)
    try:
        # 6 concurrent requests through 2 slots: queueing + slot reuse
        reqs = [eng.submit([i + 1, i + 2], max_tokens=5) for i in range(6)]
        for r in reqs:
            toks = list(_drain(r))
            assert len(toks) == 5
    finally:
        eng.shutdown()


def _drain(req):
    while True:
        item = req.out.get(timeout=60)
        if not isinstance(item, int):
            if isinstance(item, BaseException):
                raise item
            return
        yield item


def test_ttft_under_long_generation(tiny_model):
    """A new request's first token must not wait for an in-flight long
    generation to finish (the point of continuous batching)."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=4, max_len=256)
    try:
        eng.warmup()
        long_req = eng.submit([1, 2, 3], max_tokens=200)
        long_req.out.get(timeout=60)  # long one is running
        t0 = time.monotonic()
        short = eng.submit([7, 8], max_tokens=2)
        first = short.out.get(timeout=60)
        ttft = time.monotonic() - t0
        assert isinstance(first, int)
        # long_req still generating when short's first token arrived
        assert long_req.generated < 200
        assert ttft < 30  # CPU jit compile headroom; real chips: ~ms
    finally:
        eng.shutdown()


def test_llm_serve_deployment(tiny_model):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment
    from ray_tpu.utils.testing import CPU_WORKER_ENV

    ray_tpu.init(num_cpus=4, worker_env=dict(CPU_WORKER_ENV))
    try:
        dep = llm_deployment("tiny", num_slots=4, max_len=64,
                             route_prefix="/llm")
        h = serve.run(dep, timeout_s=120)
        toks = list(h.stream({"tokens": [1, 2, 3], "max_tokens": 5}))
        assert len(toks) == 5
        assert all(isinstance(t, int) for t in toks)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
