"""On the chip (``chiprun -- python tests/chip_moe_grad_check.py``; not a
pytest file: the tests here are held to the CPU).  One dropless expert layer
at the share cell's sizes (PR 39: 16,384 tokens, hidden 2,048, 8 of the
router's 64 experts of width 1,408 held, 6 a token, two shared, the held
group by position as the cell's configuration has it), in bf16: the
gradients through the Pallas kernels' ``custom_vjp`` (``moe_gmm``,
``moe_gmm_dx``, ``moe_gmm_dw``) of the input, the router and the experts'
three matrices

- against the twin's (``jax.lax.ragged_dot``'s own derivative) on the same
  sorted layout,
- against a float32 layer written without the sort (every held expert on
  every token under the mask of the router's choice, precision highest),
- and, the control, against that float32 layer with one held expert's
  output left out, which has to read far over the limit: the check sees the
  routed experts, which the benchmark cell's scalar loss does not (PERF.md
  section 7, PR 39).

Relative L2 errors; exits 1 over ``LIMIT`` or with a control under
``CONTROL_FLOOR``.  ``tiny`` as first argument: sizes for the CPU
(``tests/test_moe_train.py`` runs that)."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ray_tpu.ops import moe  # noqa: E402

#: bf16 rounding reads 0.002-0.005 on the chip (PERF.md section 6, PR 39)
LIMIT = 0.01
#: an eighth of the routed experts missing reads 0.1 and more
CONTROL_FLOOR = 0.05
BF, F32 = jnp.bfloat16, jnp.float32
SCALING = 2.446


def check(tiny: bool = False) -> dict:
    t, h, e, held, m, k = ((96, 64, 16, 4, 32, 3) if tiny
                           else (16384, 2048, 64, 8, 1408, 6))
    ks = jax.random.split(jax.random.PRNGKey(7), 9)

    def w(key, *shape, fan_in):
        return jax.random.normal(key, shape, F32) * fan_in ** -0.5

    x = jax.random.normal(ks[0], (t, h), F32).astype(BF)
    small = {"router": w(ks[1], h, e, fan_in=h), "bias": jnp.zeros((e,), F32),
             "shared_gate": w(ks[2], h, 2 * m, fan_in=h),
             "shared_in": w(ks[3], h, 2 * m, fan_in=h),
             "shared_out": w(ks[4], 2 * m, h, fan_in=2 * m)}
    stacks = {"w_gate": w(ks[5], 1, held, h, m, fan_in=h).astype(BF),
              "w_in": w(ks[6], 1, held, h, m, fan_in=h).astype(BF),
              "w_out": w(ks[7], 1, held, m, h, fan_in=m).astype(BF)}
    cot = jax.random.normal(ks[8], (t, h), F32).astype(BF).astype(F32)
    start = jnp.arange(t) % (e // held) * held

    def layer(use_kernel):
        def f(x, router, stacks):
            out = moe.moe_dropless(
                x, dict(small, router=router), stacks, 0,
                experts_per_token=k, scaling=SCALING, expert_start=start,
                use_kernel=use_kernel, interpret=tiny or None)[0]
            return (out.astype(F32) * cot).sum()
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    def dense(x, router, stacks, first=0):
        with jax.default_matmul_precision("highest"):
            x32 = x.astype(F32)
            scores = jax.nn.sigmoid(x32 @ router)
            _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores), k)
            gates = jnp.take_along_axis(scores, idx, -1)
            gates = gates / gates.sum(-1, keepdims=True) * SCALING
            out = (jax.nn.silu(x32 @ small["shared_gate"])
                   * (x32 @ small["shared_in"])) @ small["shared_out"]
            for j in range(first, held):
                gate, up, down = (stacks[n][0, j].astype(F32)
                                  for n in ("w_gate", "w_in", "w_out"))
                weight = jnp.where(idx == start[:, None] + j, gates,
                                   0.0).sum(-1)
                out = out + weight[:, None] * (
                    (jax.nn.silu(x32 @ gate) * (x32 @ up)) @ down)
            return (out * cot).sum()

    def rel(a, b):
        a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
        return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))

    names = ("x", "router", "w_gate", "w_in", "w_out")
    got = {}
    for tag, fn in (
            ("kernel", layer(True)), ("twin", layer(False)),
            ("float32", jax.jit(jax.grad(dense, argnums=(0, 1, 2)))),
            ("float32_less_an_expert", jax.jit(jax.grad(
                lambda *a: dense(*a, first=1), argnums=(0, 1, 2))))):
        t0 = time.monotonic()
        g = jax.block_until_ready(fn(x, small["router"], stacks))
        got[tag] = (g[0], g[1], g[2]["w_gate"], g[2]["w_in"], g[2]["w_out"])
        finite = all(bool(jnp.isfinite(jnp.asarray(a, F32)).all())
                     for a in got[tag])
        print(f"{tag}: {time.monotonic() - t0:.1f}s finite={finite}",
              file=sys.stderr, flush=True)
        assert finite, tag
    out = {"sizes": [t, h, e, held, m, k],
           "device": jax.devices()[0].device_kind}
    for a, b in (("kernel", "twin"), ("kernel", "float32"),
                 ("twin", "float32"), ("kernel", "float32_less_an_expert")):
        out[f"{a}_vs_{b}"] = dict(zip(names, (
            rel(ga, gb) for ga, gb in zip(got[a], got[b]))))
    sound = max(max(out[f"kernel_vs_{b}"].values())
                for b in ("twin", "float32"))
    # the missing expert's own matrices have no gradient at all there, and
    # the input's and the router's lose its share
    control = min(out["kernel_vs_float32_less_an_expert"][n]
                  for n in ("x", "router", "w_gate"))
    out.update(limit=LIMIT, worst_sound=sound, control_floor=CONTROL_FLOOR,
               least_control=control,
               ok=bool(sound < LIMIT and control > CONTROL_FLOOR))
    return out


if __name__ == "__main__":
    result = check(tiny=sys.argv[1:2] == ["tiny"])
    print("GRADCHECK " + json.dumps(result), flush=True)
    sys.exit(0 if result["ok"] else 1)
