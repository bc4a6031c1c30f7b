"""Benchmark: sharded causal-LM train step, tokens/sec/chip + MFU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

A measurement needs the chip: where ``jax.devices()`` fails or finds no TPU
the script exits non-zero with a message naming what it found.  ``--preset
debug`` and ``--allow-cpu`` are the explicit CPU rehearsal switches; a CPU
run's line carries ``"device": "cpu"`` and is not a device measurement.

Baseline semantics (BASELINE.json): the north star is >=70% of a reference H100's
tokens/sec/device on Llama-family pretrain.  Public H100 pretrain runs land around
40% MFU, so the device-neutral comparison is MFU-based:

    vs_baseline = (our MFU) / (0.70 * 0.40)

i.e. 1.0 == the 70%-of-H100 target, >1.0 beats it.  MFU is model FLOPs (6*N_active
+ attention) over the chip's peak bf16 FLOPs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from ray_tpu.utils.compile_cache import place_compile_cache


def estimate_hbm_bytes(cfg, batch: int, seq: int, n_devices: int) -> float:
    """Per-device HBM for one train step (fsdp over n devices, remat on,
    chunked cross-entropy).

    Round 1 OOMed because the estimate was `params * 12 * 1.35`, which missed
    the f32 gradients, the hoisted bf16 casts of the stacked params, and the
    f32 logits.  This models what the round-1 HLO allocation dump actually
    showed:
      * train state: f32 params (4) + f32 grads (4, coexist with state under
        donation) + adam mu/nu (8)
      * bf16 param casts: XLA hoists the `.astype(bf16)` of the loop-invariant
        stacked weights out of the layer scan (+2)
      * activations: scan carry checkpointed per layer (L*B*S*H*2) + one
        layer's transient attention scores (B*NH*S^2*2) + qkv/mlp temps
      * chunked CE: one [B, chunk, V] f32 logits block (fwd + bwd)
    """
    p = cfg.num_params()
    state = p * (4 + 4 + 8 + 2) / n_devices
    h, L, nh = cfg.hidden_size, cfg.num_layers, cfg.num_heads
    b = max(1, batch // n_devices)  # batch sharded over dp/fsdp
    carry = L * b * seq * h * 2
    scores = b * nh * seq * seq * 2
    temps = 8 * b * seq * max(h, cfg.mlp_size) * 2
    ce_chunk = 2 * b * min(512, seq) * cfg.vocab_size * 4
    return (state + carry + scores + temps + ce_chunk) * 1.10


def pick_config(args, n_devices: int, hbm_bytes: float):
    from ray_tpu.models import config as mcfg
    if args.preset == "debug":
        return mcfg.tiny(), 8, 64
    if args.preset != "auto":
        cfg = mcfg.PRESETS[args.preset]()
        return cfg, args.batch, args.seq or min(cfg.max_seq_len, 2048)
    # auto: largest Llama-family bench config (and largest batch <= requested)
    # that fits the measured HBM under the memory model above.
    for name in ("llama3-8b", "llama-1b", "llama-400m", "gpt2-124m"):
        cfg_fn = mcfg.PRESETS[name]
        seq = args.seq or (2048 if name != "gpt2-124m" else 1024)
        # batch must stay divisible by the mesh's dp*fsdp extent (= n_devices
        # here) or device_put on the batch sharding fails.
        batch = max(args.batch, n_devices)
        batch -= batch % n_devices
        while batch >= n_devices:
            if estimate_hbm_bytes(cfg_fn(), batch, seq, n_devices) < hbm_bytes:
                return cfg_fn(max_seq_len=seq), batch, seq
            batch = batch // 2 - (batch // 2) % n_devices
    return mcfg.tiny(), 8, 64


def tpu_devices(args):
    """``jax.devices()`` when they are TPUs (or an explicit CPU rehearsal
    switch is on); otherwise exit non-zero, naming what was found."""
    import jax
    try:
        devices = jax.devices()
    except Exception as e:  # backend init failed: say so, fail
        raise SystemExit(f"bench.py: no TPU: jax.devices() failed: "
                         f"{str(e).splitlines()[0][:300]}")
    d = devices[0]
    if d.platform != "tpu" and args.preset != "debug" and not args.allow_cpu:
        raise SystemExit(
            f"bench.py: no TPU: jax.devices() found platform={d.platform} "
            f"kind={d.device_kind} n={len(devices)}; pass --preset debug or "
            f"--allow-cpu for a CPU rehearsal (not a measurement)")
    return devices


# ------------------------------------------------------- chipspeed (>=1B)

#: every (splash, quant, zero) combination, off-arm first
CHIPSPEED_ARMS = [(s, q, z) for s in (False, True) for q in (False, True)
                  for z in (False, True)]


def _arm_name(splash: bool, quant: bool, zero: bool) -> str:
    on = [n for n, f in (("splash", splash), ("quant", quant),
                         ("zero", zero)) if f]
    return "+".join(on) if on else "off"


def _run_chipspeed_arm(devices, splash, quant, zero, args):
    import jax
    from ray_tpu.models import config as mcfg
    from ray_tpu.parallel import (MeshSpec, OptimizerSpec,
                                  init_sharded_state, init_zero_state,
                                  make_train_step)
    n = len(devices)
    if args.preset == "debug":
        # head_dim 128 / seq 128: the smallest shape the splash arms tile
        base = mcfg.tiny(hidden=512, heads=4, seq=128)
        batch, seq = max(8, n), 128
    else:
        # the ~1B config ROADMAP item 2 names (llama_1b counts 0.89B)
        base = mcfg.llama_1b()
        seq = args.seq or base.max_seq_len
        batch = max(args.batch, n)
    batch -= batch % n
    cfg = mcfg.TransformerConfig(
        **{**base.__dict__, "max_seq_len": seq,
           "attention_impl": "splash" if splash else "auto"})
    spec = OptimizerSpec(total_steps=max(args.steps + args.warmup, 10))
    # quant/zero schedule their own dp collectives; the off arms keep
    # today's fsdp-sharded auto path exactly
    mesh = (MeshSpec(dp=-1, fsdp=1) if (quant or zero)
            else MeshSpec(fsdp=-1)).build(devices)
    remat = None if args.remat in ("none", "None") else args.remat

    t0 = time.time()
    if zero:
        state, sh = init_zero_state(cfg, mesh, spec)
    else:
        state, sh = init_sharded_state(cfg, mesh, spec.build())
    step = make_train_step(cfg, mesh, spec.build(), sh, remat=remat,
                           grad_quant_enabled=quant,
                           zero_sharded_update=zero, opt_spec=spec)
    toks = jax.random.randint(jax.random.PRNGKey(0), (batch, seq + 1), 0,
                              cfg.vocab_size)
    batch_dict = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, batch_dict)
    float(metrics["loss"])  # the host read waits for the steps
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(args.steps):
        state, metrics = step(state, batch_dict)
    final_loss = float(metrics["loss"])
    dt = time.time() - t0
    memory = None
    try:
        ms = devices[0].memory_stats() or {}
        memory = {k: int(ms[k]) for k in
                  ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                  if k in ms} or None
    except Exception:
        pass
    tok_chip = batch * seq * args.steps / dt / n
    mfu = (tok_chip * cfg.flops_per_token(seq)
           / mcfg.detect_peak_flops(devices[0]))
    return {
        "mfu": round(mfu, 4),
        "tokens_per_sec_per_chip": round(tok_chip, 2),
        "step_ms": round(dt / args.steps * 1000, 1),
        "compile_s": round(compile_s, 1),
        "loss": round(final_loss, 4),
        "memory": memory,
        "wire_bytes_per_step": {f"{op}/{wd}": v for (op, wd), v
                                in step.collective_bytes.items()},
        "opt_state_bytes": step.opt_state_bytes,
        "model": f"{cfg.num_params() / 1e6:.0f}M",
        "batch": batch, "seq": seq, "n_devices": n,
    }


def run_chipspeed(args):
    """The ~1B arm matrix: (splash, quant, zero) x {on, off}, checkpointed
    per arm (an arm that dies loses no earlier arm), one final JSON line +
    BENCH_CHIPSPEED.json.  An arm that raised is recorded as aborted, the
    matrix goes on, and the exit code is non-zero."""
    metric = "chipspeed_1b_mfu"
    devices = tpu_devices(args)
    ckpt = "BENCH_CHIPSPEED_partial.json"
    partial = {}
    if not args.fresh and os.path.exists(ckpt):
        try:
            with open(ckpt) as f:
                partial = json.load(f)
            done = [k for k, v in partial.items()
                    if isinstance(v, dict) and "aborted" not in v]
            if done:
                print(f"# resuming: arms {done} checkpointed, skipping",
                      flush=True)
        except Exception:
            partial = {}
    for splash, quant, zero in CHIPSPEED_ARMS:
        key = _arm_name(splash, quant, zero)
        cached = partial.get(key)
        if isinstance(cached, dict) and "aborted" not in cached:
            print(f"# {key}: checkpointed, skipping", flush=True)
            continue
        try:
            res = _run_chipspeed_arm(devices, splash, quant, zero, args)
        except Exception as e:  # an OOM must not lose earlier arms
            res = {"aborted": str(e).splitlines()[0][:300]}
        partial[key] = res
        print(f"# {key}: {json.dumps(res)}", flush=True)
        with open(ckpt, "w") as f:
            json.dump(partial, f, indent=1)
    complete = {k: v for k, v in partial.items()
                if isinstance(v, dict) and "aborted" not in v}
    best_key = max(complete, key=lambda k: complete[k].get("mfu", 0.0),
                   default=None)
    out = {
        "metric": metric,
        "value": complete[best_key]["mfu"] if best_key else None,
        "unit": "mfu",
        "best_arm": best_key,
        "vs_off": (round(complete[best_key]["mfu"]
                         / complete["off"]["mfu"], 4)
                   if best_key and complete.get("off", {}).get("mfu")
                   else None),
        "arms": partial,
        "platform": devices[0].platform,
        "device": devices[0].device_kind,
        "n_devices": len(devices),
    }
    with open("BENCH_CHIPSPEED.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    aborted = sorted(set(partial) - set(complete))
    if aborted:
        sys.exit(f"bench.py: arms aborted: {aborted}")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="auto",
                   help="auto|debug|llama-1b|gpt2-124m|llama3-8b|mixtral-8x7b")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=0)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--remat", default="save_acts",
                   help="full|save_acts|save_mlp|dots|none — see "
                        "models/transformer.py remat_policy")
    p.add_argument("--allow-cpu", action="store_true",
                   help="run on CPU devices instead of failing (still "
                        "CPU-sized via --preset; auto on CPU is unwise)")
    p.add_argument("--chipspeed", action="store_true",
                   help="run the >=1B (splash, quant, zero) arm matrix "
                        "with per-arm checkpointing instead of the single "
                        "headline config")
    p.add_argument("--fresh", action="store_true",
                   help="ignore the chipspeed checkpoint and rerun all arms")
    args = p.parse_args()

    place_compile_cache()
    if args.chipspeed:
        run_chipspeed(args)
        return

    import jax
    devices = tpu_devices(args)
    from ray_tpu.models.config import detect_peak_flops
    from ray_tpu.parallel import (MeshSpec, init_sharded_state, make_optimizer,
                                  make_train_step)
    n = len(devices)
    hbm = 16e9
    try:
        stats = devices[0].memory_stats()
        hbm = stats.get("bytes_limit", hbm)
    except Exception:
        pass
    peak = detect_peak_flops(devices[0])

    cfg, batch, seq = pick_config(args, n, hbm)

    mesh = MeshSpec(fsdp=-1).build(devices)
    opt = make_optimizer(total_steps=max(args.steps + args.warmup, 10))
    t0 = time.time()
    state, sh = init_sharded_state(cfg, mesh, opt)
    remat = None if args.remat in ("none", "None") else args.remat
    step = make_train_step(cfg, mesh, opt, sh, remat=remat)
    toks = jax.random.randint(jax.random.PRNGKey(0), (batch, seq + 1), 0,
                              cfg.vocab_size)
    batch_dict = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, batch_dict)
    float(metrics["loss"])  # the host read waits for the steps
    compile_s = time.time() - t0

    t0 = time.time()
    for _ in range(args.steps):
        state, metrics = step(state, batch_dict)
    final_loss = float(metrics["loss"])
    dt = time.time() - t0

    # Instrumented tail pass: per-step walls with a forcing read each —
    # the runtime-comparable goodput fields (train/observability.py
    # reports the same shapes at runtime).  Kept OUT of the headline
    # timed region: the per-step host read stalls the dispatch pipeline.
    step_walls = []
    for _ in range(min(args.steps, 5)):
        s0 = time.time()
        state, metrics = step(state, batch_dict)
        float(metrics["loss"])
        step_walls.append(time.time() - s0)
    step_walls.sort()
    step_p50 = step_walls[len(step_walls) // 2]
    memory = None
    try:
        ms = devices[0].memory_stats() or {}
        memory = {k: int(ms[k]) for k in
                  ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                  if k in ms} or None
    except Exception:
        pass
    # goodput over this invocation: productive (timed-loop) step time over
    # step time + the compile it paid — compile_s stays split out of every
    # step median above, exactly like the runtime tracker
    goodput = dt / max(compile_s + dt, 1e-9)

    tokens_per_step = batch * seq
    tok_s = tokens_per_step * args.steps / dt
    tok_s_chip = tok_s / n
    flops_per_token = cfg.flops_per_token(seq)
    mfu = (tok_s_chip * flops_per_token) / peak
    vs_baseline = mfu / (0.70 * 0.40)

    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 4),
        "mfu": round(mfu, 4),
        "model": f"{cfg.num_params() / 1e6:.0f}M",
        "batch": batch, "seq": seq, "steps": args.steps,
        "n_devices": n,
        "platform": devices[0].platform,
        "device": devices[0].device_kind,
        "peak_bf16_tflops": peak / 1e12,
        "compile_s": round(compile_s, 1),
        "step_ms": round(dt / args.steps * 1000, 1),
        "step_ms_p50": round(step_p50 * 1000, 1),
        "goodput": round(goodput, 4),
        "memory": memory,
        "loss": round(final_loss, 4),
    }))


if __name__ == "__main__":
    main()
