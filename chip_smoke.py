"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # one host with four: the sharded train
                                    # path and its one-device reference only

Drives the two hot paths once, through the entry points a user calls, at the
full width and depth of a model the repo ships, with weights from a seed:

* **train** (llama-400m, batch 8 x seq 2048): the README quick start,
  ``MeshSpec(fsdp=-1).build(jax.devices())`` -> ``init_sharded_state`` ->
  ``make_train_step(remat="save_acts")``, one compiling step plus five more
  on a repeated batch.
* **decode numerics** (same process, so the chip has one owner):
  ``decode.prefill`` + ``decode_step`` and the paged twins against
  ``transformer.apply`` with plain attention, on logits.
* **serve**, dense then paged, from this parent, which never imports JAX:
  ``ray_tpu.init()`` -> ``serve.run(llm_deployment("llama-400m", ...))`` with
  the replica asking for the host's ``TPU`` resource; eight concurrent
  streamed requests, one of them through the HTTP ingress.

Every phase checks what comes out, and a phase that fails ends the run with a
non-zero exit code: nothing is caught and survived.  A run that finds no TPU
fails at once and prints no result.  One process owns a chip at a time: the
train child exits before the first replica starts, and each replica's
process has exited before the next lifecycle begins.

The last line of standard output is the result and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Everything above it is information (each line names the platform it came
from); the times are not results.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
import random
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

#: What every phase must find.  A rehearsal at toy sizes on the CPU patches
#: these module constants from a scratch script; the program has no switch.
WANT_PLATFORM = "tpu"
SEED = 0
MODEL, BATCH, SEQ, STEPS = "llama-400m", 8, 2048, 6
SLOTS, MAX_LEN, NEW_TOKENS, N_REQUESTS = 16, 1024, 32, 8
PROMPT_LENS = (100, 400, 900)       # three pinned lengths, the other five
#                                     seeded between the first and the last,
WANT_BUCKETS = (128, 512, 1024)     # so that these prefill buckets compile
MODEL_4 = "llama-1b"                # does not fit one chip with fp32 Adam
STEPS_4 = 4

# Tolerances, set from what the chip showed (PR 22, TPU v5 lite; see PERF.md):
#: |loss(kernel attention) - loss(plain attention)|, same params and batch
TOL_ATTN_LOSS = 1e-3
#: max |logit difference| of prefill/decode_step against transformer.apply
TOL_LOGITS = 0.15
#: |loss(fsdp over four chips) - loss(one device)|, same params and batch
TOL_SHARDED_LOSS = 1e-4

KERNEL = "tpu_custom_call"          # a compiled Pallas kernel in the HLO text


def check(ok: bool, what: str):
    """A check that does not hold ends the run: message on stderr, code 1."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say(phase: str, dev: str, msg: str):
    print(f"[{phase}] on {dev}: {msg}", flush=True)


# ------------------------------------------------------- the device's owner

def _devices(want_count: int):
    """jax.devices(), or fail naming what was found.  Only a process that is
    meant to own the chip calls this."""
    import jax
    devs = jax.devices()
    d = devs[0]
    found = f"platform={d.platform} kind={d.device_kind!r} count={len(devs)}"
    if d.platform != WANT_PLATFORM:
        raise SystemExit(f"chip_smoke: no TPU: jax.devices() found {found}")
    check(len(devs) == want_count,
          f"this run needs {want_count} device(s), found {found}")
    return devs, f"{d.platform} ({d.device_kind} x{len(devs)})"


def _device_dict(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _mem(dev) -> dict:
    return dev.memory_stats() or {}


def _train(cfg, devs, dev, batch_size, seq, steps, phase):
    """Quick-start train path on ``devs``; returns (first-step loss, state,
    shardings, mesh)."""
    import jax

    from ray_tpu.parallel import (MeshSpec, init_sharded_state,
                                  make_optimizer, make_train_step)

    mesh = MeshSpec(fsdp=-1).build(devs)
    opt = make_optimizer(total_steps=1000)
    t0 = time.time()
    state, sh = init_sharded_state(cfg, mesh, opt, seed=SEED)
    step = make_train_step(cfg, mesh, opt, sh, remat="save_acts")
    toks = _tokens(cfg, batch_size, seq)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    say(phase, dev, f"model={cfg.num_params() / 1e6:.0f}M params "
        f"(cfg.num_params()={cfg.num_params()}) layers={cfg.num_layers} "
        f"hidden={cfg.hidden_size} batch={batch_size} seq={seq} "
        f"attention_impl={cfg.attention_impl!r} mesh={dict(mesh.shape)} "
        f"init_s={time.time() - t0:.1f}")

    # Compile ahead of the first call, to have the compiler's own account of
    # the step before it runs: an out-of-memory then comes with its numbers.
    t0 = time.time()
    on_dev = {k: jax.device_put(v, step.batch_sharding)
              for k, v in batch.items()}
    compiled = step._jitted.lower(state, on_dev).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    say(phase, dev, f"compile_s={time.time() - t0:.1f} "
        f"kernel_calls={text.count(KERNEL)} per-device bytes: "
        f"arguments={mem.argument_size_in_bytes} "
        f"temporaries={mem.temp_size_in_bytes} "
        f"outputs={mem.output_size_in_bytes} "
        f"aliased={mem.alias_size_in_bytes} "
        f"limit={_mem(devs[0]).get('bytes_limit')}")
    if WANT_PLATFORM == "tpu":
        check(KERNEL in text, "the compiled train step holds no Pallas kernel "
              f"({KERNEL}): attention was dispatched to the plain path")
    if len(devs) > 1:
        check("all-gather" in text and ("reduce-scatter" in text
                                        or "all-reduce" in text),
              "sharded step compiled without all-gather and a gradient "
              "reduce-scatter/all-reduce")

    losses = []
    for i in range(steps):
        t0 = time.time()
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))   # the read waits for the step
        say(phase, dev, f"step={i} loss={losses[-1]:.4f} "
            f"grad_norm={float(metrics['grad_norm']):.3f} "
            f"wall_s={time.time() - t0:.3f}"
            + (" (compiles)" if i == 0 else ""))
    # What a random model of this repo must score: init_params gives the LM
    # head a 1/sqrt(hidden) scale under a unit-RMS final norm, so the logits
    # at init are ~N(0, 1) and E[loss] = ln(vocab) + 1/2, not ln(vocab).
    want = math.log(cfg.vocab_size) + 0.5
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - want) <= 0.02 * want,
          f"first loss {losses[0]:.4f} not within 2% of ln(vocab)+1/2="
          f"{want:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall: first {losses[0]:.4f} last {losses[-1]:.4f}")
    say(phase, dev, f"first loss {losses[0]:.4f} vs ln(vocab)+1/2={want:.4f} "
        f"(unit-variance logits at init; ln(vocab)={want - 0.5:.4f}); "
        f"last {losses[-1]:.4f}; peak_bytes_in_use="
        f"{[_mem(d).get('peak_bytes_in_use') for d in devs]}")
    return losses[0], state, sh, mesh


def _tokens(cfg, batch_size, seq):
    """The one seeded [B, S+1] batch every loss of a run is taken on."""
    import jax
    return jax.random.randint(jax.random.PRNGKey(SEED + 1),
                              (batch_size, seq + 1), 0, cfg.vocab_size)


def _forward_loss(cfg, mesh, state_sh, params, toks, impl=None):
    """Forward loss through the repo's eval step (no remat, no update).
    ``state_sh.params`` is the parameters' sharding tree."""
    from ray_tpu.parallel.train_step import make_eval_step
    if impl is not None:
        cfg = dataclasses.replace(cfg, attention_impl=impl)
    return float(make_eval_step(cfg, mesh, state_sh)(
        params, {"tokens": toks})["loss"])


def train_and_decode():
    """The one-chip child: owns the chip for the train and decode phases."""
    import jax

    from ray_tpu.models import config as mcfg
    devs, dev = _devices(1)
    cfg = mcfg.PRESETS[MODEL]()
    _, state, sh, mesh = _train(cfg, devs, dev, BATCH, SEQ, STEPS, "train")
    # kernel attention against plain attention: same (trained) params, same
    # batch, forward only
    toks = _tokens(cfg, BATCH, SEQ)
    auto = _forward_loss(cfg, mesh, sh, state.params, toks)
    plain = _forward_loss(cfg, mesh, sh, state.params, toks, "plain")
    say("train", dev, f"forward loss attention_impl='auto'={auto:.6f} "
        f"'plain'={plain:.6f} |diff|={abs(auto - plain):.2e} "
        f"(tolerance {TOL_ATTN_LOSS})")
    check(abs(auto - plain) <= TOL_ATTN_LOSS,
          f"kernel and plain attention disagree: {auto} vs {plain}")
    del state
    jax.clear_caches()
    _decode_numerics(cfg, dev)
    # for the parent, which stays off jax (and so off ray_tpu.models)
    print("REPORT " + json.dumps({"device": _device_dict(devs),
                                  "vocab": cfg.vocab_size}), flush=True)


def _decode_numerics(cfg, dev):
    """prefill + decode_step, dense and paged, against transformer.apply with
    plain attention on the same tokens: logits, not token ids (argmax on
    random weights flips on bf16 noise)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import decode, paged_decode, transformer

    s = min(1024, MAX_LEN)          # the bucket where dense prefill is long
    lens = np.array([s - 24, (2 * s) // 3], np.int32)
    params = transformer.init_params(jax.random.PRNGKey(SEED), cfg,
                                     dtype=jnp.bfloat16)
    rng = np.random.RandomState(SEED)
    tokens = rng.randint(1, cfg.vocab_size, (2, s)).astype(np.int32)
    slots = jnp.arange(2)
    plain_cfg = dataclasses.replace(cfg, attention_impl="plain")
    ref_fn = jax.jit(lambda p, t: transformer.apply(p, t, plain_cfg)[0])

    def ref_at(toks, pos):          # reference logits [2, V] at pos[b]
        return np.asarray(ref_fn(params, toks))[np.arange(2), pos]

    ref_prefill = ref_at(tokens, lens - 1)
    say("decode", dev, f"reference: transformer.apply, plain attention, "
        f"S={s}; logits std={ref_prefill.std():.3f}")

    prefill = jax.jit(lambda p, c, t, ln, sl: decode.prefill(
        p, c, t, ln, sl, cfg))
    dense_cache = decode.init_kv_cache(cfg, 2, s)
    text = prefill.lower(params, dense_cache, tokens, lens,
                         slots).compile().as_text()
    say("decode", dev, f"dense prefill S={s} compiled attention: "
        + ("flash kernel" if KERNEL in text else "plain XLA")
        + f" ({KERNEL} x{text.count(KERNEL)})")
    if WANT_PLATFORM == "tpu" and s >= 1024:
        check(KERNEL in text, "dense prefill at S=1024 compiled no kernel")

    mp = s // 64
    paged_cache = paged_decode.init_paged_cache(cfg, 2 * mp + 1, 64, 2, mp)
    paged_cache["block_table"] = jnp.arange(1, 2 * mp + 1,
                                            dtype=jnp.int32).reshape(2, mp)
    # the same two calls on either tree: the cache says what it is
    step = jax.jit(lambda p, c, t, a: decode.decode_step(p, c, t, a, cfg))
    for name, cache in (("dense", dense_cache), ("paged", paged_cache)):
        cache, logits = prefill(params, cache, tokens, lens, slots)
        d_pre = float(np.abs(np.asarray(logits) - ref_prefill).max())
        nxt = np.asarray(logits).argmax(-1).astype(np.int32)
        toks2 = tokens.copy()
        toks2[np.arange(2), lens] = nxt
        cache, logits2 = step(params, cache, nxt, jnp.ones((2,), bool))
        d_dec = float(np.abs(np.asarray(logits2) - ref_at(toks2, lens)).max())
        say("decode", dev, f"{name}: max|logit diff| prefill={d_pre:.4f} "
            f"decode_step={d_dec:.4f} (tolerance {TOL_LOGITS}) "
            f"finite={bool(np.isfinite(np.asarray(logits2)).all())}")
        check(np.asarray(logits2).shape == (2, cfg.vocab_size),
              f"{name} decode logits shape {np.asarray(logits2).shape}")
        check(np.isfinite(np.asarray(logits2)).all(), f"{name} logits nan/inf")
        check(max(d_pre, d_dec) <= TOL_LOGITS,
              f"{name} decode path disagrees with transformer.apply: "
              f"prefill {d_pre}, decode_step {d_dec}")


def four_chips():
    """The sharded path and what it is compared with, and no other phase."""
    import types

    import jax

    from ray_tpu.models import config as mcfg
    from ray_tpu.models import sharding as shard_rules
    from ray_tpu.models import transformer
    from ray_tpu.parallel import MeshSpec

    devs, dev = _devices(4)
    cfg = mcfg.PRESETS[MODEL_4]()
    seq = min(SEQ, cfg.max_seq_len)

    # the reference first, while device 0 is empty: the same seeded fp32
    # parameters whole on one device, forward only
    one = MeshSpec(fsdp=-1).build(devs[:1])
    rep = jax.sharding.NamedSharding(one, jax.sharding.PartitionSpec())
    params_sh = jax.tree.map(lambda _: rep, jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(SEED), cfg)))
    params1 = jax.jit(lambda: transformer.init_params(
        jax.random.PRNGKey(SEED), cfg), out_shardings=params_sh)()
    ref = _forward_loss(cfg, one, types.SimpleNamespace(params=params_sh),
                        params1, _tokens(cfg, BATCH, seq))
    say("train4", f"{devs[0].platform} ({devs[0].device_kind} x1, the "
        "reference)", f"forward loss of the seeded params on one device="
        f"{ref:.6f} bytes_in_use={_mem(devs[0]).get('bytes_in_use')}")
    del params1

    first, state, sh, mesh = _train(cfg, devs, dev, BATCH, seq, STEPS_4,
                                    "train4")
    say("train4", dev, f"first-step loss (fsdp over 4)={first:.6f} one-device "
        f"reference={ref:.6f} |diff|={abs(first - ref):.2e} "
        f"(tolerance {TOL_SHARDED_LOSS})")
    check(abs(first - ref) <= TOL_SHARDED_LOSS,
          f"sharded loss {first} disagrees with one-device loss {ref}")

    # every device holds its share
    specs = shard_rules.logical_param_specs(cfg)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    n_sharded = n_replicated = 0
    for (path, leaf), spec in zip(
            jax.tree_util.tree_leaves_with_path(state.params),
            jax.tree.leaves(specs, is_leaf=is_spec)):
        name = jax.tree_util.keystr(path)
        check(len(leaf.sharding.device_set) == 4,
              f"{name} lives on {len(leaf.sharding.device_set)} devices")
        split = math.prod(mesh.shape[a] for ax in spec if ax is not None
                          for a in ((ax,) if isinstance(ax, str) else ax))
        shard = leaf.addressable_shards[0].data
        check(shard.size * split == leaf.size,
              f"{name}: rule {spec} splits {split}-way but a device holds "
              f"{shard.size} of {leaf.size} elements")
        n_sharded += split > 1
        n_replicated += split == 1
    in_use = [_mem(d).get("bytes_in_use") for d in devs]
    say("train4", dev, f"params: {n_sharded} leaves split 4-way, "
        f"{n_replicated} replicated by rule (norm scales); "
        f"bytes_in_use per device={in_use}")
    check(n_sharded > 0, "no parameter is sharded")
    if WANT_PLATFORM == "tpu":
        check(min(in_use) >= 0.8 * max(in_use),
              f"devices do not hold comparable shares: {in_use}")
    return devs


# -------------------------------------------------- the serve phase (parent)

def _tail_worker_logs(session_dir: str, lines: int = 60):
    for path in sorted(glob.glob(os.path.join(session_dir, "logs",
                                              "worker-*.log"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        if tail:
            print(f"----- tail of {path}", flush=True)
            sys.stdout.writelines(tail)
            print("-----", flush=True)


def _wait_exited(pids, timeout_s: float = 60.0) -> float:
    """Seconds until none of ``pids`` is a live process (gone, or a zombie:
    an exited process holds no device)."""
    def alive(pid):
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0] != "Z"
        except OSError:
            return False

    t0 = time.monotonic()
    while any(alive(p) for p in pids):
        check(time.monotonic() - t0 < timeout_s,
              f"worker processes {[p for p in pids if alive(p)]} still "
              f"alive {timeout_s:.0f}s after shutdown")
        time.sleep(0.05)
    return time.monotonic() - t0


def _http_stream(url: str, payload: dict, timeout_s: float):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        check(r.status == 200, f"HTTP ingress answered {r.status}")
        return [json.loads(line) for line in r.read().decode().split()]


def serve_phase(paged: bool, want: dict, vocab: int):
    """One full cluster lifecycle: init, deploy, drive, check, tear down,
    and see the worker processes gone.  ``want`` is the device the train
    child reported, ``vocab`` the model's vocabulary size."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.serve.llm import llm_deployment

    phase = "serve-paged" if paged else "serve-dense"
    rng = random.Random(SEED + (1 if paged else 0))
    lens = list(PROMPT_LENS) + [
        rng.randint(PROMPT_LENS[0], PROMPT_LENS[-1])
        for _ in range(N_REQUESTS - len(PROMPT_LENS))]
    prompts = [[rng.randrange(1, vocab) for _ in range(n)] for n in lens]

    t_phase = time.time()
    info = ray_tpu.init()
    try:
        check("jax" not in sys.modules, "the serve parent imported jax")
        tpus = ray_tpu.cluster_resources().get("TPU", 0)
        t0 = time.time()
        h = serve.run(llm_deployment(
            MODEL, num_slots=SLOTS, max_len=MAX_LEN, route_prefix="/llm",
            max_concurrent_queries=64, health_check_timeout_s=600.0,
            ray_actor_options={"num_tpus": tpus} if tpus else {},
            engine_kwargs={"paged": paged}), http=True, timeout_s=600.0)
        deploy_s = time.time() - t0
        http = serve.http_config()
        url = f"http://{http['host']}:{http['port']}/llm"

        outs, errs, walls = [None] * N_REQUESTS, [], [0.0] * N_REQUESTS

        def client(i):
            body = {"tokens": prompts[i], "max_tokens": NEW_TOKENS}
            t = time.time()
            try:
                outs[i] = (_http_stream(url, body, 600.0) if i == 0
                           else list(h.stream(body, timeout_s=600.0)))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errs.append((i, e))
            walls[i] = time.time() - t

        t0 = time.time()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(N_REQUESTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        drive_s = time.time() - t0
        if errs:
            raise errs[0][1]
        stats = h.stats.remote().result(timeout_s=60)
        dev = (f"{stats['platform']} ({stats['device_kind']} "
               f"x{stats['device_count']}, the replica's own report)")
        say(phase, dev, f"deploy_s={deploy_s:.1f} drive_s={drive_s:.1f} "
            f"requests={N_REQUESTS} (1 over HTTP {url}) prompt_lens={lens} "
            f"new_tokens={NEW_TOKENS} per-request wall_s="
            f"{[round(w, 1) for w in walls]}")
        say(phase, dev, f"stats: steps={stats['steps']} "
            f"tokens_out={stats['tokens_out']} "
            f"prefill_buckets={stats['prefill_buckets']} "
            f"admit_batches={stats['admit_batches']} "
            f"kv_pages={stats.get('kv_pages')} "
            f"cluster TPU resource={tpus}")
        for i, out in enumerate(outs):
            check(len(out) == NEW_TOKENS and all(
                isinstance(t, int) and 0 <= t < vocab for t in out),
                f"request {i} returned {out!r}")
        check(stats["steps"] > 0, "the engine took no decode step")
        check(set(WANT_BUCKETS) <= set(stats["prefill_buckets"]),
              f"prefill buckets compiled: {stats['prefill_buckets']}, "
              f"wanted at least {WANT_BUCKETS}")
        got = {"platform": stats["platform"], "kind": stats["device_kind"],
               "count": stats["device_count"]}
        check(got == want, f"the replica ran on {got}, the train child on "
              f"{want}")
        if WANT_PLATFORM == "tpu":
            check(tpus == stats["device_count"],
                  f"cluster_resources()['TPU']={tpus} but the replica's jax "
                  f"sees {stats['device_count']} device(s)")
        pids = [w.pid for w in api._state.node_agent.workers.values()]
    except BaseException:
        _tail_worker_logs(info["session_dir"])
        raise
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    waited = _wait_exited(pids)
    say(phase, dev, f"torn down: {len(pids)} worker processes gone "
        f"{waited:.2f}s after shutdown returned; "
        f"phase_s={time.time() - t_phase:.1f}")


# ----------------------------------------------------------------- the run

def _run_child(call: str) -> dict:
    """Run ``chip_smoke.<call>()`` in a child that owns the chip; echo its
    lines; return what it reported."""
    proc = subprocess.Popen(
        [sys.executable, "-c", f"import chip_smoke; chip_smoke.{call}()"],
        cwd=REPO, stdout=subprocess.PIPE, text=True)
    report = None
    try:
        for line in proc.stdout:
            if line.startswith("REPORT "):
                report = json.loads(line[len("REPORT "):])
            else:
                print(line, end="", flush=True)
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        sys.exit(rc)
    check(report is not None, f"{call} reported nothing")
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train path on four chips and "
                         "its one-device reference")
    args = ap.parse_args()
    t_start = time.time()

    from ray_tpu import native
    from ray_tpu.core.common import detect_node_resources
    from ray_tpu.utils.compile_cache import cache_entries, place_compile_cache

    cache_dir = place_compile_cache()   # before any process is started
    before = cache_entries(cache_dir)
    print(f"[host] compile cache: JAX_COMPILATION_CACHE_DIR={cache_dir} "
          f"entries_before={before}", flush=True)
    libs = {"shm_pool": native.load_shm_pool(),
            "submit_plane": native.load_submit_plane(),
            "crc32c": native.load_crc32c()}
    print("[host] native_loaded: " + " ".join(
        f"{k}={v is not None}" for k, v in libs.items()), flush=True)
    check(all(v is not None for v in libs.values()),
          "a native library did not build here (g++); the pure-Python "
          "fallback is not what this smoke proves")
    print(f"[host] detect_node_resources()={detect_node_resources()} "
          f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r} "
          f"(found without importing jax)", flush=True)

    if args.chips == 4:
        device = _device_dict(four_chips())
    else:
        report = _run_child("train_and_decode")
        device = report["device"]
        serve_phase(False, device, report["vocab"])
        serve_phase(True, device, report["vocab"])
        check("jax" not in sys.modules, "the parent imported jax")
    print(f"[host] compile cache: {cache_dir} entries_before={before} "
          f"entries_after={cache_entries(cache_dir)}; "
          f"total_s={time.time() - t_start:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
