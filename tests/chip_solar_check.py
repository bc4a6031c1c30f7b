"""On the chip (``chiprun -- python tests/chip_solar_check.py [seeds]
[variants]``; not a pytest file: the tests here are held to the CPU).  The
harness's own comparison, the one that decides ``correct`` in the cell
``serve-kda-moe-reasoning-closed`` (``benchmark/serve_app.py``
``BenchLLMServer._check_reference`` with the limits of the configuration's
``serve.check``), called here on seeded weights without an engine around
it, once on the program as it is and once on each of the two lower-precision
controls ISSUE 44 asks to see fail:

- ``sound``: the program as it is: has to come out ``ok``;
- ``state_bf16``: the delta rule's state rounded to bf16 after the prefill
  and after every decode step: has to come out not ``ok``;
- ``gmm_bf16``: the grouped expert products accumulated in bf16 (the
  running sum over the contraction rounded to bf16 every 8 terms; the chip's
  matrix unit itself only accumulates in float32, and the kernel's one
  rounding of a finished product to bf16 is the sound program's): not
  ``ok`` either;
- ``gmm_bf16_128``, the same every 128 terms (one pass of the matrix unit):
  reported, and held to nothing (it reads within 2% of the sound program).

One JSON line a seed and variant, then ``SOLARCHECK {...}``; exits 1 where
the sound program fails the comparison or a control passes it.  Arguments:
seeds and variants' names; ``tiny`` first: the tests' toy configuration and
its limits, for the CPU (a rehearsal of the control flow: nothing is held
to the verdicts there)."""

import contextlib
import json
import os
import sys
import types
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import loadgen  # noqa: E402
from benchmark.lib.manifest import load_model  # noqa: E402
from benchmark.serve_app import BenchLLMServer  # noqa: E402
from ray_tpu.ops import kda, moe  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "solar-open2-250b-serve-l4-e40.json")
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "configs",
                    "tiny-solar.json")
KIND = os.path.join(REPO, "benchmark", "models", "solar_open2.py")


def bf16(x):
    """x (float32) rounded to bf16's 8 bits of mantissa, in float32.  Not
    ``astype`` there and back: the compiler is allowed excess precision and
    drops that pair (my chip run, PR 44: a control built on it read the
    sound program's numbers to the last bit)."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7)


def gmm_bf16(every):
    """``moe.moe_gmm``'s result with the contraction summed in a bf16
    accumulator, ``every`` products at a time."""
    def gmm(x, weights, layer, tile_expert, tiles, tile, **_):
        experts = weights[0].shape[1]
        sizes = jnp.zeros((experts,), jnp.int32).at[tile_expert].add(tile)
        ws = [jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
              for w in weights]

        def product(w):
            terms = min(every, x.shape[1])

            def some(i, acc):
                part = jax.lax.ragged_dot(
                    jax.lax.dynamic_slice_in_dim(x, i * terms, terms, 1),
                    jax.lax.dynamic_slice_in_dim(w, i * terms, terms, 1),
                    sizes, preferred_element_type=jnp.float32)
                return bf16(acc + bf16(part))
            return jax.lax.fori_loop(
                0, x.shape[1] // terms, some,
                jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32))

        out = product(ws[0])
        if len(ws) == 2:
            out = jax.nn.silu(out) * product(ws[1])
        return out.astype(x.dtype)
    return gmm


def state_bf16():
    """``ops.kda``'s two entry points with the state they return rounded to
    bf16: after a prefill and after every decode step."""
    chunk, step = kda.kda_chunk_fwd, kda.kda_recurrent_step

    def chunk_fwd(*a, **kw):
        o, state = chunk(*a, **kw)
        return o, bf16(state)

    def recurrent_step(*a, **kw):
        state, o = step(*a, **kw)
        return bf16(state), o
    return {"kda_chunk_fwd": chunk_fwd, "kda_recurrent_step": recurrent_step}


#: what a variant puts in place of the program's own, by module, and the
#: verdict the comparison has to give it (None: held to nothing)
VARIANTS = {
    "sound": ({}, True),
    "state_bf16": ({kda: state_bf16}, False),
    "gmm_bf16": ({moe: lambda: {"moe_gmm": gmm_bf16(8)}}, False),
    "gmm_bf16_128": ({moe: lambda: {"moe_gmm": gmm_bf16(128)}}, None),
}


@contextlib.contextmanager
def patched(name):
    """The program with a variant's replacements, while it is traced: the
    compared run and the run inside the reference's jit alike."""
    with contextlib.ExitStack() as stack:
        for mod, make in VARIANTS[name][0].items():
            stack.enter_context(mock.patch.multiple(mod, **make()))
        yield


def main(argv):
    tiny = argv[:1] == ["tiny"]
    seeds = [int(a) for a in argv[tiny:] if a.isdigit()] or [2026100101]
    names = [a for a in argv[tiny:] if a in VARIANTS] or list(VARIANTS)
    with open(TINY if tiny else CONFIG) as f:
        doc = json.load(f)
    model = load_model(KIND)
    cfg = model.program_config(doc)
    ok = True
    for seed in seeds:
        folded = loadgen.fold_seed(seed)
        params = jax.jit(lambda key: model.init_params(
            key, cfg, jnp.bfloat16))(jax.random.PRNGKey(folded))
        # what ``_check_reference`` reads of the server it is a method of
        server = types.SimpleNamespace(
            doc=doc, seed=folded, model=model, engine=types.SimpleNamespace(
                cfg=cfg, params=params, compute_dtype=jnp.bfloat16))
        for name in names:
            with patched(name):
                row = BenchLLMServer._check_reference(server)
            want = VARIANTS[name][1]
            held = tiny or want is None or row["ok"] == want
            ok &= held
            print(json.dumps({"seed": seed, "variant": name, **row,
                              "ok_wanted": want, "as_wanted": bool(held)}),
                  flush=True)
    dev = jax.devices()[0]
    print("SOLARCHECK " + json.dumps({
        "ok": bool(ok), "limits": {k: doc["serve"]["check"][k] for k in (
            "tol_max_abs", "tol_rms", "prompt_len", "decode_steps")},
        "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
