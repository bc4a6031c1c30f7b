"""On the chip (``chiprun -- python tests/chip_exaone_check.py [seeds]
[variants] [steps=N]``; not a pytest file: the tests here are held to the
CPU).  The comparison that decides ``correct`` in the cell
``serve-swa-moe-mtp-longreason-closed`` (``benchmark/serve_app.py``
``BenchLLMServer._check_reference``: a prefill and decode steps through the
kind's entry points on seeded weights, against the kind's float32 reference
on its own), made here without an engine around it, at the configuration's
own sizes and limits, on the program as it is and on the controls ISSUE 50
asks to see fail:

- ``sound``: has to pass the configuration's limits;
- ``ring_exact``: the window layers' rings allocated with exactly the
  window's 128 rows, no margin for the verify step's second token: the
  draft's row replaces position ``t - 127``, which the kept token still
  reads.  A fault of the cache's layout; has to fail;
- ``kv_fp8``: the keys and values rounded to float8 e4m3's three bits of
  mantissa as they leave the projections (what the cache holds and what the
  prefill attends over): a lower precision; has to fail;
- ``gmm_bf16``: the grouped expert products accumulated in bf16 (the running
  sum over the contraction rounded to bf16 every 8 terms), and
  ``scores_bf16``: the decode kernels' scores rounded to bf16 before the
  softmax (rows and rings alike): reported, not held to a verdict.  On the
  configuration's seeded weights (sharp attention: every q norm's scale 4)
  the bf16 scores read 1.14-1.17 times their own seed's sound reading and
  inside the limits, since the rounding of q and k ahead of the scores is
  the larger part; the bf16 accumulator was read on the first hand-in's
  weights alone, inside the sound band there (PERF.md section 6, PR 50):
  this comparison hides both (``MUST_FAIL`` says which controls refuse the
  script).

The compared run goes through what the cell times: the banded flash forward
and the flash forward of a 1,536 row for a prompt of 1,300, then rounds of
the speculative step with the next token forced (a verify step of two
tokens over rows and rings, the draft rolled back, the block's pass), 520 of
them, so that the ring of 256 wraps twice.  One JSON line a seed and
variant, then ``EXAONECHECK {...}``; exits 1 where the sound program fails or
a control in ``MUST_FAIL`` passes.  ``tiny`` first: the tests' toy
configuration, for the CPU (a rehearsal of the control flow: nothing is held
to the verdicts there)."""

import contextlib
import json
import os
import sys
import time
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib.manifest import load_model  # noqa: E402
from ray_tpu.ops import decode_attention as da  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "k-exaone-236b-a23b-serve-l8-e8.json")
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "configs",
                    "tiny-exaone.json")
KIND = os.path.join(REPO, "benchmark", "models", "exaone_moe.py")
MUST_FAIL = ("ring_exact", "kv_fp8")


def bf16(x):
    """x (float32) rounded to bf16's 8 bits of mantissa, in float32.  Not
    ``astype`` there and back: the compiler is allowed excess precision and
    drops that pair (tests/chip_solar_check.py, PR 44)."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7)


def gmm_bf16(every):
    """``moe.moe_gmm``'s result with the contraction summed in a bf16
    accumulator, ``every`` products at a time (``tests/chip_nano_check.py``'s
    control, with the gate-and-up form's SiLU product)."""
    def gmm(x, weights, layer, tile_expert, tiles, tile, **_):
        experts = weights[0].shape[1]
        sizes = jnp.zeros((experts,), jnp.int32).at[tile_expert].add(tile)
        terms = min(every, x.shape[1])

        def product(w):
            w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)

            def some(i, acc):
                part = jax.lax.ragged_dot(
                    jax.lax.dynamic_slice_in_dim(x, i * terms, terms, 1),
                    jax.lax.dynamic_slice_in_dim(w, i * terms, terms, 1),
                    sizes, preferred_element_type=jnp.float32)
                return bf16(acc + bf16(part))

            return jax.lax.fori_loop(
                0, x.shape[1] // terms, some,
                jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32))

        outs = [product(w) for w in weights]
        out = jax.nn.silu(outs[0]) * outs[1] if len(outs) == 2 else outs[0]
        return out.astype(x.dtype)
    return gmm


def scores_bf16():
    """The decode kernels' block update with its scores rounded to bf16."""
    def update(q_row, k, v, seen, m, l, acc, *, scale, softcap):
        # (inside the kernel a cast there and back stays: Mosaic has no
        # ``reduce_precision`` and drops nothing)
        s = (jax.lax.dot_general(
            q_row, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale).astype(
                jnp.bfloat16).astype(jnp.float32)
        s = jnp.where(seen(s.shape), s, da.NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                alpha * acc + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    return update


def kv_fp8():
    """``decode._qkv`` with the keys and values it hands on (to the cache and
    to the prefill's attention alike) rounded to float8 e4m3's 3 bits of
    mantissa: a cache a deployment might keep in float8."""
    from ray_tpu.models import decode
    real = decode._qkv

    def qkv(*a, **kw):
        q, k, v = real(*a, **kw)
        e4m3 = lambda x: jax.lax.reduce_precision(    # noqa: E731
            x.astype(jnp.float32), 4, 3).astype(x.dtype)
        return q, e4m3(k), e4m3(v)

    return mock.patch.object(decode, "_qkv", qkv)


def patched(name):
    if name == "scores_bf16":
        return mock.patch.object(da, "_block_update", scores_bf16())
    if name == "gmm_bf16":
        return mock.patch.object(moe, "moe_gmm", gmm_bf16(8))
    if name == "kv_fp8":
        return kv_fp8()
    return contextlib.nullcontext()


def compared_run(model, cfg, params, toks, n_prompt, ring):
    """``_check_reference``'s run: logits [1 + steps, V]."""
    cache_len = -(-(len(toks) + 1) // 128) * 128
    cache = model.init_cache(cfg, 1, cache_len, jnp.bfloat16, ring=ring)
    cache, lg = jax.jit(lambda p, c, t, ln, sl: model.prefill(
        p, c, t, ln, sl, cfg))(params, cache, toks[None, :n_prompt],
                               np.array([n_prompt], np.int32),
                               np.array([0], np.int32))
    got = [np.asarray(lg)[0]]
    step = jax.jit(lambda p, c, t, a: model.decode_step(p, c, t, a, cfg))
    for i in range(n_prompt, len(toks)):
        cache, lg = step(params, cache, toks[i:i + 1], np.ones((1,), bool))
        got.append(np.asarray(lg)[0])
    return np.stack(got)


def main(argv):
    tiny = argv[:1] == ["tiny"]
    with open(TINY if tiny else CONFIG) as f:
        doc = json.load(f)
    model = load_model(KIND)
    cfg, chk = model.program_config(doc), doc["serve"]["check"]
    names = ("sound", "ring_exact", "kv_fp8", "gmm_bf16", "scores_bf16")
    seeds = [int(a) for a in argv if a.isdigit()] or [1]
    variants = [a for a in argv if a in names] or list(names)
    steps = next((int(a[6:]) for a in argv if a.startswith("steps=")),
                 chk["decode_steps"])
    n_prompt = chk["prompt_len"]
    dtype = jnp.float32 if tiny else jnp.bfloat16
    ok = True
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    params = ref = None
    for seed in seeds:
        del params, ref         # one model's weights on the chip at a time
        jax.clear_caches()
        params = jax.jit(lambda k: model.init_params(k, cfg, dtype))(
            jax.random.PRNGKey(seed))
        toks = np.random.default_rng([seed, 7]).integers(
            1, cfg.vocab_size, size=n_prompt + steps).astype(np.int32)
        pos = jnp.arange(n_prompt - 1, n_prompt + steps)
        ref = np.asarray(jax.jit(lambda p, t: model.logits(
            p, t, doc, pos))(params, toks))
        for name in variants:
            t0 = time.monotonic()
            ring = cfg.sliding_window if name == "ring_exact" else None
            with patched(name):
                got = compared_run(model, cfg, params, toks, n_prompt, ring)
            diff = got - ref
            out = {"seed": seed, "variant": name, "steps": steps,
                   "max_abs_diff": float(np.abs(diff).max()),
                   "rms_diff": float(np.sqrt((diff ** 2).mean())),
                   "rms_first_64": float(np.sqrt((diff[:64] ** 2).mean())),
                   "rms_last_64": float(np.sqrt((diff[-64:] ** 2).mean())),
                   "ref_std": float(ref.std()),
                   "finite": bool(np.isfinite(got).all()),
                   "seconds": round(time.monotonic() - t0, 1)}
            out["passes"] = bool(out["finite"]
                                 and out["max_abs_diff"] <= chk["tol_max_abs"]
                                 and out["rms_diff"] <= chk["tol_rms"])
            if not tiny and (out["passes"] != (name == "sound")) and (
                    name == "sound" or name in MUST_FAIL):
                ok = False
            print(json.dumps(out), flush=True)
            with open(os.path.join(REPO, "chiprun_out",
                                   "exaone_check.jsonl"), "a") as f:
                f.write(json.dumps(out) + "\n")
    print("EXAONECHECK " + json.dumps({
        "ok": ok, "tol_rms": chk["tol_rms"],
        "tol_max_abs": chk["tol_max_abs"],
        "device": str(jax.devices()[0].device_kind)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
