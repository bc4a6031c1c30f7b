"""The flash kernels alone on the chip at a latent head's shapes (PR 45; the
backward one kernel since PR 48).

    chiprun -- python tests/chip_flash_widths.py [out_dir]

Times ``flash_fwd`` and ``flash_dkv`` (the whole backward: dq, dk and dv
from one score tile) at ``(2, 8192, 16, 16)`` heads, causal, bf16, for pairs of (query/key width, value width): 192 / 128
as the latent kind hands them over, 256 / 128 (q and k padded, the fallback
ISSUE 45 names), 256 / 256 (what the kind padded to before) and 128 / 128
(Mistral's).  Each pair is traced for ``STEPS`` forward + backward calls and
the kernels' self time is read with the benchmark's own reduction
(``benchmark/lib/trace.py``).  Results at 192 / 128 are held to the padded
call's (zeros change no product).  The last line is ``FLASHWIDTHS {...}``
with ms a call by kernel and pair, and ``"ok"``.  A CPU run is refused: a
time comes from the chip.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark.lib import trace
from ray_tpu.ops.flash_attention import flash_attention

SHAPE = (2, 8192, 16, 16)            # batch, positions, heads, kv heads
PAIRS = ((192, 128), (256, 128), (256, 256), (128, 128))
KERNELS = ("flash_fwd", "flash_dkv")
STEPS = 5


def operands(d_qk, d_v, key=0):
    b, s, h, kv = SHAPE
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    return (jax.random.normal(ks[0], (b, s, h, d_qk), jnp.bfloat16),
            jax.random.normal(ks[1], (b, s, kv, d_qk), jnp.bfloat16),
            jax.random.normal(ks[2], (b, s, kv, d_v), jnp.bfloat16),
            jax.random.normal(ks[3], (b, s, h, d_v), jnp.bfloat16))


@jax.jit
def fwd_bwd(q, k, v, g):
    """The output and the three gradients under the cotangent ``g``."""
    out, pull = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True),
                        q, k, v)
    return (out,) + pull(g)


def pad_to(a, width):
    return jnp.pad(a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))


def timed(pair, out_dir):
    """ms a call of each kernel and of every device operation of the jitted
    call (``all_ops``: the kernels, the transposes in and out, ``delta``),
    self time over ``STEPS`` traced calls."""
    args = operands(*pair)
    jax.block_until_ready(fwd_bwd(*args))
    where = os.path.join(out_dir, "trace-%d-%d" % pair)
    t0 = time.perf_counter()
    with jax.profiler.trace(where):
        for _ in range(STEPS):
            jax.block_until_ready(fwd_bwd(*args))
    wall = time.perf_counter() - t0
    rows = trace.summarize(trace.load_xplane(trace.find_xplane(where)),
                           wall)["ops"]
    # outside a scan the instruction carries the transformation's name too
    # (``transpose_jvp_flash_dkv__``)
    ms = {k: 1e3 * trace.seconds_matching(
        rows, k + r"_* \[pallas\]$")[0] / STEPS for k in KERNELS}
    ms["all_ops"] = 1e3 * sum(r[1] for r in rows) / STEPS
    return ms


def same_results():
    """192 / 128 against the same operands padded with zeros to 256 / 256:
    the largest difference of the output and of each gradient, relative to
    the padded call's largest value.  The padded query is scaled so that
    both calls scale the scores by 192 ** -0.5."""
    q, k, v, g = operands(192, 128, key=1)
    got = fwd_bwd(q, k, v, g)
    want = fwd_bwd(pad_to(q * (256 / 192) ** 0.5, 256).astype(q.dtype),
                   pad_to(k, 256), pad_to(v, 256), pad_to(g, 256))
    want = (want[0][..., :128], want[1][..., :192] * (256 / 192) ** 0.5,
            want[2][..., :192], want[3][..., :128])
    return [float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
                  / jnp.abs(b.astype(jnp.float32)).max())
            for a, b in zip(got, want)]


def main():
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/flash_widths"
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"no TPU: jax.devices() found {device.platform}")
    result = {"device": device.device_kind, "shape": SHAPE, "steps": STEPS,
              "ms": {"%d/%d" % p: timed(p, out_dir) for p in PAIRS},
              "rel_diff_out_dq_dk_dv": same_results()}
    # the scaled query is rounded to bf16 once more on the padded side
    result["ok"] = max(result["rel_diff_out_dq_dk_dv"]) < 0.02
    print("FLASHWIDTHS " + json.dumps(result))
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
