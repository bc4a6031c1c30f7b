"""The latent kind's 8,192 admit program on the chip, walked in counted chunks
against the whole row on the same weights (PR 47).

    chiprun -- python tests/chip_latent_chunks.py [out_dir]

At the long-context cell's sizes (``xing4.0-29b-a4b-serve-l7``: 1 dense + 6
expert layers at published widths, bf16, a latent cache of 8,192 positions a
slot; 4 slots here, the program's time does not go by them) one row of the
8,192 bucket through ``decode.prefill`` twice: as the engine runs it since PR
47, chunks of 2,048 (``decode.prefill_width``), and as the parent ran it, the
whole row (``chunk=8192``: a bucket of fewer than four chunks).  For prompts
of ``LENGTHS`` tokens:

* results: last-token logits rms and max of the difference, the latent rows
  and rotary keys where the prompt wrote them (the share of positions whose
  row is the whole row's to the bit, by layer), the routers' choices that
  differ ((layer, position) pairs, by expert layer), ``length``; and, as the
  measure of what two programs of one model differ by anyway, the same
  comparison between two programs the parent had, a ``CONTROL``-token prompt
  as a whole row of the 4,096 bucket and of the 8,192 bucket;
* time: ms a row by the host clock around one blocking call (median of
  ``REPEATS``), and the kernels' self time a row from a trace of each
  program at 6,144 (``flash_fwd_rows`` against ``flash_fwd``, ``moe_gmm``),
  read with the benchmark's own reduction (``benchmark/lib/trace.py``).

The last line is ``LATENTCHUNKS {...}`` with ``"ok"``: every prompt's logits
within ``TOL_RMS`` of the whole row's, its first token the whole row's, the
first layer's rows equal to the bit and the first expert layer's routing the
whole row's at all but ``TOL_FLIPS`` of its positions (a near-tie that a
rounding turns over; the later layers' routers then see another input and
turn over too, as between any two programs: the control).  A CPU run is
refused: a time comes from the chip.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import trace
from benchmark.lib.manifest import load_model
from ray_tpu.models import decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "xing4.0-29b-a4b-serve-l7.json")
WANT_PLATFORM = "tpu"
BUCKET, SLOTS, SLOT = 8192, 4, 2
SHORTEST, CHUNK = decode.PREFILL_CHUNK, 2048    # what the experts make of it
LENGTHS = (4100, 5000, 6144, 7000, 8192)     # 3, 3, 3, 4 and 4 chunks
TRACED = 6144                   # the cell's longest prompt: the clip
REPEATS = 5
CONTROL = 4000                  # a prompt both of the parent's buckets take
# logits of std 1.0; two programs round at other points, and 0.07-0.14% of
# the first expert layer's near-ties fell the other way (PERF.md, PR 47)
TOL_RMS, TOL_FLIPS = 0.05, 0.005
KERNELS = ("flash_fwd_rows", "flash_fwd", "moe_gmm")


def main():
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/latent_chunks"
    device = jax.devices()[0]
    if device.platform != WANT_PLATFORM:
        sys.exit(f"no TPU: jax.devices() found {device.platform}")
    kind = load_model(os.path.join(REPO, "benchmark", "models", "xing4_0.py"))
    with open(CONFIG) as f:
        cfg = kind.program_config(json.load(f))
    params = kind.init_params(jax.random.PRNGKey(47), cfg, jnp.bfloat16)
    empty = decode.init_kv_cache(cfg, SLOTS, BUCKET, jnp.bfloat16,
                                 expert_choices=True)
    assert decode.prefill_width(empty, BUCKET, cfg, SHORTEST) == CHUNK
    programs = {
        name: jax.jit(lambda p, c, t, n, chunk=chunk: decode.prefill(
            p, c, t, n, jnp.array([SLOT]), cfg, chunk=chunk))
        for name, chunk in (("chunks", SHORTEST), ("whole", BUCKET))}

    def row(name, length, bucket=BUCKET):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :length] = np.random.default_rng(length).integers(
            1, cfg.vocab_size, length)
        return programs[name](params, empty, toks,
                              np.array([length], np.int32))

    def ms(name, length):
        jax.block_until_ready(row(name, length))
        times = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(row(name, length))
            times.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(times)

    def traced(name):
        """Self time a row of each kernel, and of every device operation."""
        where = os.path.join(out_dir, "trace-" + name)
        t0 = time.perf_counter()
        with jax.profiler.trace(where):
            for _ in range(REPEATS):
                jax.block_until_ready(row(name, TRACED))
        wall = time.perf_counter() - t0
        rows = trace.summarize(trace.load_xplane(trace.find_xplane(where)),
                               wall)["ops"]
        out = {k: 1e3 * trace.seconds_matching(
            rows, k + r" \[pallas\]$")[0] / REPEATS for k in KERNELS}
        out["all_ops"] = 1e3 * sum(r[1] for r in rows) / REPEATS
        out["top"] = [[r[0], round(1e3 * r[1] / REPEATS, 3)]
                      for r in rows[:12]]
        return out

    def compared(length, got, want):
        """``got`` against ``want``, each a program's (cache, logits) for
        one prompt of ``length``."""
        (got, lg), (want, lg_w) = got, want
        diff = np.asarray(lg - lg_w, np.float32)
        said, said_w = (np.sort(np.asarray(c[decode.CHOICES][:, SLOT,
                                                             :length]), -1)
                        for c in (got, want))
        flipped = (said != said_w).any(-1)
        same = (got["latent"][:, SLOT, :length]
                == want["latent"][:, SLOT, :length]).all(-1)
        walked = -(-length // CHUNK) * CHUNK
        return {
            "logits_rms": float(np.sqrt((diff ** 2).mean())),
            "logits_max": float(np.abs(diff).max()),
            "logits_std": float(np.asarray(lg_w, np.float32).std()),
            "first_token_equal": bool(lg.argmax() == lg_w.argmax()),
            "rows_equal_share_by_layer": np.asarray(
                same.mean(-1), np.float64).round(5).tolist(),
            "flipped_pairs": float(flipped.mean()),
            "flipped_by_layer": flipped.mean(-1).round(5).tolist(),
            "length": [int(c["length"][SLOT]) for c in (got, want)],
            "unwalked_left_alone": not bool(
                jnp.any(got["latent"][:, SLOT, walked:])),
        }

    result = {"device": device.device_kind, "bucket": BUCKET,
              "chunk": CHUNK,
              "compared": {n: compared(n, row("chunks", n), row("whole", n))
                           for n in LENGTHS[:3]},
              "control_whole_%d_against_whole_%d" % (BUCKET // 2, BUCKET):
              compared(CONTROL, row("whole", CONTROL, BUCKET // 2),
                       row("whole", CONTROL)),
              "ms_a_row": {name: {n: ms(name, n) for n in LENGTHS}
                           for name in programs},
              "traced_ms_a_row_at_%d" % TRACED: {
                  name: traced(name) for name in programs}}
    result["ok"] = all(
        c["logits_rms"] < TOL_RMS and c["first_token_equal"]
        and c["rows_equal_share_by_layer"][0] == 1.0
        and c["flipped_by_layer"][0] < TOL_FLIPS
        and c["length"][0] == c["length"][1] and c["unwalked_left_alone"]
        for c in result["compared"].values())
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print("LATENTCHUNKS " + json.dumps(result))
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
