"""On the chip (``chiprun -- python tests/chip_granite_check.py [seeds]
[variants]``; not a pytest file: the tests here are held to the CPU).  The
comparison that decides ``correct`` in the cell
``serve-ssm-dense-agents-closed`` (``benchmark/serve_app.py``
``BenchLLMServer._check_reference``: a prefill of 1,900 tokens in the 2,048
row and 512 decode steps through the kind's entry points on seeded weights,
against the kind's float32 reference, at the configuration's own limits),
called here without an engine around it, on the program as it is and on the
controls ISSUE 53 asks to see fail **through those same limits**.  With no
router the reference needs nothing of the compared run, so the harness's
own method is the whole comparison:

- ``sound``: has to pass;
- ``state_bf16``: the state-space state rounded to bf16 after the prefill
  and after every decode step (``jax.lax.reduce_precision`` outside the
  kernel; the sibling's two controls, ``tests/chip_nano_check.py``): has to
  fail;
- ``state_lost``: the prefill's state not carried into the decode steps
  (zeros): has to fail;
- ``no_residual`` / ``no_embedding`` / ``no_attention`` / ``no_logits``: the
  program run with that one published multiplier left out (the field at 0:
  absent), the reference with all four: each has to fail.

One JSON line a seed and variant, then ``GRANITECHECK {...}``; exits 1 where
the sound program fails or a control passes.  Arguments: seeds, variants'
names, ``steps=N`` for another count of decode steps; ``tiny`` first: the
tests' toy configuration, for the CPU (a rehearsal of the control flow:
nothing is held to the verdicts there)."""

import contextlib
import dataclasses
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
from chip_nano_check import state_bf16, state_lost  # noqa: E402
from ray_tpu.ops import ssd  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "granite-4.0-h-micro-serve-l40.json")
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "configs",
                    "tiny-granite.json")
KIND = os.path.join(REPO, "benchmark", "models", "granitemoehybrid.py")


#: a variant: what it puts in place of ``ops.ssd``'s own, and the fields of
#: the program's configuration it sets (a multiplier at 0 is left out)
VARIANTS = {
    "sound": (None, {}),
    "state_bf16": (state_bf16, {}),
    "state_lost": (state_lost, {}),
    "no_residual": (None, {"residual_multiplier": 0.0}),
    "no_embedding": (None, {"embedding_multiplier": 0.0}),
    "no_attention": (None, {"attention_multiplier": 0.0}),
    "no_logits": (None, {"logits_scaling": 0.0}),
}
DEFAULT = ("sound", "state_bf16", "state_lost", "no_residual")


@contextlib.contextmanager
def patched(name):
    """The program with a variant's replacements, while it is traced."""
    make = VARIANTS[name][0]
    with (mock.patch.multiple(ssd, **make()) if make
          else contextlib.nullcontext()):
        yield


def main(argv):
    tiny = argv[:1] == ["tiny"]
    seeds = [int(a) for a in argv[tiny:] if a.isdigit()] or [2026100301]
    names = [a for a in argv[tiny:] if a in VARIANTS] or list(DEFAULT)
    with open(TINY if tiny else CONFIG) as f:
        doc = json.load(f)
    chk = doc["serve"]["check"]
    for arg in argv:                # steps=256: another length of the check
        if arg.startswith("steps="):
            chk["decode_steps"] = int(arg[6:])
    model = load_model(KIND)
    cfg = model.program_config(doc)
    ok = True
    for seed in seeds:
        folded = loadgen.fold_seed(seed)
        params = jax.jit(lambda key: model.init_params(
            key, cfg, jnp.bfloat16))(jax.random.PRNGKey(folded))
        for name in names:
            t0 = time.monotonic()
            run = dataclasses.replace(cfg, **VARIANTS[name][1])
            with patched(name):
                # the harness's own method, on the variant's program
                row = BenchLLMServer._check_reference(types.SimpleNamespace(
                    doc=doc, seed=folded, model=model,
                    engine=types.SimpleNamespace(
                        cfg=run, params=params, compute_dtype=jnp.bfloat16)))
            held = row["ok"] == (name == "sound")
            ok &= bool(held or tiny)
            print(json.dumps({"seed": seed, "variant": name, **row,
                              "as_wanted": bool(held),
                              "wall_s": time.monotonic() - t0}), flush=True)
    dev = jax.devices()[0]
    print("GRANITECHECK " + json.dumps({
        "ok": bool(ok), "limits": {k: chk[k] for k in (
            "tol_max_abs", "tol_rms", "prompt_len", "decode_steps")},
        "device": {"platform": dev.platform, "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
