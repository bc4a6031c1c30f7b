"""On the chip (``chiprun -- python tests/chip_sambay_check.py [seeds]
[variants]``; not a pytest file: the tests here are held to the CPU).  The
comparison that decides ``correct`` in the cell ``serve-sambay-longcot-closed``
(``benchmark/serve_app.py`` ``BenchLLMServer._check_reference``: a prefill of
1,900 tokens in the 2,048 row and 512 decode steps through the kind's entry
points on seeded weights, against the kind's float32 reference, at the
configuration's own limits), called here without an engine around it, on the
program as it is and on the controls ISSUE 60 asks to see fail **through
those same limits**.  With no router the reference needs nothing of the
compared run, so the harness's own method is the whole comparison:

- ``sound``: has to pass;
- ``kv_fp8``: every key and value rounded to float8's e4m3 (4 bits of
  exponent, 3 of mantissa: ``jax.lax.reduce_precision`` outside the
  kernels) as it leaves its projection, so the rows, the rings and the
  prefill's own attention all hold the next precision below the bf16 the
  configuration states for them: has to fail;
- ``state_bf16``: the selective scan's float32 state rounded to bf16 after
  the prefill and after every decode step (``jax.lax.reduce_precision``
  outside the kernel): REPORTED, held to no verdict.  It reads 1.006-1.11
  times its own seed's sound rms (eight seeds, PR 60) where the sound
  readings of twenty-one seeds lie 27% apart, so no limit in absolute terms tells
  it from a sound run of another seed (PERF.md section 6, PR 60);
- ``memory_gated``: the memory handed to the gated memory units taken AFTER
  the Mamba layer's gate (``y * silu(z)``) in place of before: has to fail;
- ``lam_fixed``: ``lam`` fixed at ``lam0`` (the four learned vectors read
  as zeros) in every attention layer: has to fail;
- ``cross_own_kv``: the cross layers reading keys and values through a
  projection of their own (layer 17's rows times a square block of the
  layer's own ``W_q``) in place of layer 17's rows as they lie: has to fail.

One JSON line a seed and variant, then ``SAMBAYCHECK {...}``; exits 1 where
the sound program fails or a control passes.  Arguments: seeds, variants'
names, ``steps=N`` for another count of decode steps, ``times`` for each
timed entry point's milliseconds beside the comparison; ``tiny`` first: the
tests' toy configuration, for the CPU (a rehearsal of the control flow:
nothing is held to the verdicts there)."""

import contextlib
import json
import os
import sys
import time
import types
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import loadgen  # noqa: E402
from benchmark.lib.manifest import load_model  # noqa: E402
from benchmark.serve_app import BenchLLMServer  # noqa: E402
from ray_tpu.models import decode, hybrid  # noqa: E402
from ray_tpu.ops import selective_scan  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "phi-4-mini-flash-reasoning-serve-l32.json")
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "configs",
                    "tiny-phi4flash.json")
KIND = os.path.join(REPO, "benchmark", "models", "phi4flash.py")


def bf16(x):
    """float32 rounded to bf16's 8 bits of mantissa, kept as float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def state_bf16():
    chunk = selective_scan.selective_scan_chunk_fwd
    step = selective_scan.selective_scan_step

    def chunk_fwd(*a, **kw):
        y, state = chunk(*a, **kw)
        return y, bf16(state)

    def one_step(*a, **kw):
        state, y = step(*a, **kw)
        return bf16(state), y
    return {selective_scan: {"selective_scan_chunk_fwd": chunk_fwd,
                             "selective_scan_step": one_step}}


def kv_fp8():
    sound = decode._qkv

    def qkv(*a, **kw):
        q, k, v = sound(*a, **kw)
        fp8 = lambda x: jax.lax.reduce_precision(     # noqa: E731
            x, exponent_bits=4, mantissa_bits=3)
        return q, fp8(k), fp8(v)
    return {decode: {"_qkv": qkv}}


def memory_gated():
    sound = hybrid._ssm1_out

    def out(y, u, z, mp):
        result, memory = sound(y, u, z, mp)
        return result, memory * jax.nn.silu(z)
    return {hybrid: {"_ssm1_out": out}}


def lam_fixed():
    sound = decode.diff_combine

    def combine(o, ap, cfg, depth):
        return sound(o, {k: jnp.zeros_like(v) if k.startswith("lam_") else v
                         for k, v in ap.items()}, cfg, depth)
    return {decode: {"diff_combine": combine}}


def cross_own_kv():
    step, row = decode.cross_attention, decode.cross_row

    def own(rows, ap):
        c = rows.shape[-1]
        return (rows.astype(jnp.float32)
                @ ap["wq"][:c, :c].astype(jnp.float32)).astype(rows.dtype)

    def cross_attention(y, ap, cfg, k_all, v_all, *rest):
        out, _, _ = step(y, ap, cfg, own(k_all, ap), own(v_all, ap), *rest)
        return out, k_all, v_all

    def cross_row(y, ap, cfg, k, v, *rest):
        return row(y, ap, cfg, own(k, ap), own(v, ap), *rest)
    return {decode: {"cross_attention": cross_attention,
                     "cross_row": cross_row}}


VARIANTS = {"sound": None, "kv_fp8": kv_fp8, "state_bf16": state_bf16,
            "memory_gated": memory_gated, "lam_fixed": lam_fixed,
            "cross_own_kv": cross_own_kv}
#: the variants that are reported and held to no verdict
REPORTED = ("state_bf16",)


@contextlib.contextmanager
def patched(name):
    """The program with a variant's replacements, while it is traced."""
    with contextlib.ExitStack() as stack:
        for mod, names in (VARIANTS[name]() if VARIANTS[name] else {}).items():
            stack.enter_context(mock.patch.multiple(mod, **names))
        yield


def timed(model, cfg, params, doc):
    """Milliseconds a call of each timed entry point at the check's sizes
    (one slot): the 2,048 row's prefill and a decode step."""
    import numpy as np
    chk = doc["serve"]["check"]
    n = chk["prompt_len"]
    toks = np.ones((1, n), np.int32)
    cache = model.init_cache(cfg, 1, 2560, jnp.bfloat16)
    pre = jax.jit(lambda p, c, t, ln, sl: model.prefill(p, c, t, ln, sl, cfg))
    step = jax.jit(lambda p, c, t, a: model.decode_step(p, c, t, a, cfg),
                   donate_argnums=(1,))
    args = (np.array([n], np.int32), np.array([0], np.int32))
    out = {}
    cache2, lg = pre(params, cache, toks, *args)
    jax.block_until_ready(lg)
    t0 = time.monotonic()
    for _ in range(3):
        cache2, lg = pre(params, cache, toks, *args)
    jax.block_until_ready(lg)
    out["prefill_row_ms"] = (time.monotonic() - t0) / 3 * 1e3
    tok, act = np.ones((1,), np.int32), np.ones((1,), bool)
    cache2, lg = step(params, cache2, tok, act)
    jax.block_until_ready(lg)
    t0 = time.monotonic()
    for _ in range(20):
        cache2, lg = step(params, cache2, tok, act)
    jax.block_until_ready(lg)
    out["decode_step_one_slot_ms"] = (time.monotonic() - t0) / 20 * 1e3
    return out


def main(argv):
    tiny = argv[:1] == ["tiny"]
    seeds = [int(a) for a in argv[tiny:] if a.isdigit()] or [2026100501]
    names = [a for a in argv[tiny:] if a in VARIANTS] or list(VARIANTS)
    with open(TINY if tiny else CONFIG) as f:
        doc = json.load(f)
    chk = doc["serve"]["check"]
    for arg in argv:                # steps=256: another length of the check
        if arg.startswith("steps="):
            chk["decode_steps"] = int(arg[6:])
    model = load_model(KIND)
    cfg = model.program_config(doc)
    dtype = jnp.float32 if tiny else jnp.bfloat16
    ok = True
    for seed in seeds:
        folded = loadgen.fold_seed(seed)
        params = jax.jit(lambda key: model.init_params(
            key, cfg, dtype))(jax.random.PRNGKey(folded))
        if "times" in argv:
            print(json.dumps({"seed": seed, **timed(model, cfg, params, doc)}),
                  flush=True)
        for name in names:
            t0 = time.monotonic()
            with patched(name):
                # the harness's own method, on the variant's program
                row = BenchLLMServer._check_reference(types.SimpleNamespace(
                    doc=doc, seed=folded, model=model,
                    engine=types.SimpleNamespace(
                        cfg=cfg, params=params, compute_dtype=dtype)))
            held = row["ok"] == (name == "sound") or name in REPORTED
            ok &= bool(held or tiny)
            print(json.dumps({"seed": seed, "variant": name, **row,
                              "as_wanted": bool(held),
                              "wall_s": time.monotonic() - t0}), flush=True)
    dev = jax.devices()[0]
    print("SAMBAYCHECK " + json.dumps({
        "ok": bool(ok), "limits": {k: chk[k] for k in (
            "tol_max_abs", "tol_rms", "prompt_len", "decode_steps")},
        "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
