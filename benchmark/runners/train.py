"""Runner of a ``kind: train`` configuration: one process that holds every
chip of the cell, through the quick-start path (``MeshSpec`` -> the body of
``init_sharded_state`` -> ``make_train_step``).  Seeded synthetic token
batches are made on the host, a new one each step, placed one step ahead;
each step ends with a blocking read of its loss."""

from __future__ import annotations

import math
import os
import time

from ..lib import loadgen, manifest, rollup
from ..lib.peaks import peaks_for
from .common import CellFailed, compact, dump, result_line, say

#: steps of the traced span, unless the traffic file says otherwise
TRACE_STEPS, TRACE_START_SHARE = 5, 0.4


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
        t_process_start: float, dump_path=None):
    want = cell.config.get("platform", "tpu")
    doc, tr, job = cell.config, cell.config["train"], cell.traffic
    model = cell.model

    from ray_tpu.utils.compile_cache import cache_entries, place_compile_cache
    cache_dir = place_compile_cache()
    say(f"compile cache {cache_dir}: {cache_entries(cache_dir)} entries")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from ray_tpu.parallel import MeshSpec, make_optimizer, make_train_step
    from ray_tpu.parallel.train_step import TrainState, state_shardings

    from ..lib import trace as trace_lib

    devs = jax.devices()
    found = (f"platform={devs[0].platform} kind={devs[0].device_kind!r} "
             f"count={len(devs)}")
    if devs[0].platform != want or len(devs) != cell.chips:
        raise CellFailed(f"cell {cell.name!r} needs {cell.chips} {want} "
                         f"device(s); jax.devices() found {found}")
    # ``setup_s`` starts here, once the machine has handed over its chips:
    # the 16.7-19.7 s before are imports and the attach of four chips, vary
    # by 3 s from run to run on unchanged code and are none of the program's,
    # while what follows reads 7.86-7.94 s (PERF.md section 6, PR 34)
    t_attached = time.monotonic()
    attach_s = t_attached - t_process_start
    say(f"devices: {found}; attached {attach_s:.2f}s after process start "
        f"(imports and the machine handing over its chips: not in setup_s)")
    seed = loadgen.fold_seed(seed)
    cfg = model.program_config(doc)
    batch_size, seq = int(tr["global_batch"]), int(tr["sequence_length"])
    tokens_per_step = batch_size * seq

    t0 = time.monotonic()
    mesh = MeshSpec(**tr["mesh"]).build(devs)
    opt = make_optimizer(**tr.get("optimizer", {}))

    # ``init_sharded_state`` with the key as an argument: as the repo has
    # it, the seed is closed over, a constant of the jitted init, so every
    # new --seed would compile it anew (PERF.md, Open questions)
    def init_fn(key):
        params = model.init_params(key, cfg, jnp.float32)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    key = jax.random.PRNGKey(seed)
    sh = state_shardings(cfg, mesh, opt, jax.eval_shape(init_fn, key))
    state = jax.jit(init_fn, out_shardings=sh)(key)
    step = make_train_step(cfg, mesh, opt, sh, remat=tr["remat"])
    jax.block_until_ready(state)
    say(f"state on the mesh {dict(mesh.shape)} in "
        f"{time.monotonic() - t0:.1f}s; params={model.num_params(doc)}")

    def host_batch(i: int) -> dict:
        if job["data"] != "uniform_tokens":
            raise CellFailed(f"train runner: unknown data {job['data']!r}")
        toks = np.random.default_rng([seed, i]).integers(
            0, cfg.vocab_size, size=(batch_size, seq + 1), dtype=np.int32)
        return {"tokens": toks[:, :-1], "targets": toks[:, 1:], "_all": toks}

    def place(b: dict) -> dict:
        return {k: jax.device_put(v, step.batch_sharding)
                for k, v in b.items() if not k.startswith("_")}

    # the plain reference's loss on the first batch, on the initial
    # parameters under the state's own shardings (a sequence per chip)
    t0 = time.monotonic()
    first = host_batch(0)
    ref_loss = float(jax.jit(
        lambda p, t: jax.vmap(lambda s: model.loss(p, s, doc))(t).mean(),
        in_shardings=(sh.params, step.batch_sharding))(
            state.params, first["_all"]))
    say(f"reference loss on the first batch {ref_loss:.6f} in "
        f"{time.monotonic() - t0:.1f}s")

    # the compiler's own account of the step, ahead of its first call
    t0 = time.monotonic()
    on_dev = place(first)
    mem = step._jitted.lower(state, on_dev).compile().memory_analysis()
    program_bytes = int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                        + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    say(f"step compiled ahead in {time.monotonic() - t0:.1f}s; per-device "
        f"bytes: arguments={mem.argument_size_in_bytes} "
        f"temporaries={mem.temp_size_in_bytes} "
        f"outputs={mem.output_size_in_bytes} "
        f"aliased={mem.alias_size_in_bytes} -> {program_bytes}")

    # warm-up: the first step (which is the one compared with the
    # reference) and one more, so that every buffer is in its steady place
    t0 = time.monotonic()
    state, metrics = step(state, on_dev)
    first_loss = float(metrics["loss"])
    state, metrics = step(state, place(host_batch(1)))
    losses_warm = [first_loss, float(metrics["loss"])]
    say(f"two warm-up steps in {time.monotonic() - t0:.1f}s; losses "
        f"{losses_warm}")
    compiled_before = (step._jitted._cache_size(), cache_entries(cache_dir))

    trace_dir = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                             f"bench_trace_{os.getpid()}")
    trace_at = float(job.get("trace", {}).get(
        "start_s", TRACE_START_SHARE * seconds))
    trace_steps = int(job.get("trace", {}).get("steps", TRACE_STEPS))
    tracing, traced = None, None

    def stop_trace(tr: dict) -> dict:
        tr["t1"] = time.monotonic()
        jax.profiler.stop_trace()
        tr["steps"] = len(step_ends) - tr["first_step"]
        return tr

    i = 2
    nxt = place(host_batch(i))
    epoch = time.monotonic()
    setup_s = epoch - t_attached
    say(f"set-up done; window {seconds:.0f}s; setup_s={setup_s:.2f} "
        f"({attach_s:.2f} to attach the chips before it)")
    step_ends, losses, started = [], [], 0
    while True:
        now = time.monotonic() - epoch
        if now >= seconds:
            break
        if trace and tracing is None and traced is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir)
            tracing = {"t0": time.monotonic(), "first_step": len(step_ends)}
        started += 1
        with TraceAnnotation("bench:dispatch_step"):
            state, metrics = step(state, nxt)
        with TraceAnnotation("bench:make_and_place_next_batch"):
            i += 1
            nxt = place(host_batch(i))
        with TraceAnnotation("bench:read_loss"):
            losses.append(float(metrics["loss"]))
        step_ends.append(time.monotonic() - epoch)
        if tracing and len(step_ends) - tracing["first_step"] >= trace_steps:
            traced, tracing = stop_trace(tracing), None
    if tracing:
        traced = stop_trace(tracing)
    compiled_after = (step._jitted._cache_size(), cache_entries(cache_dir))

    roll = rollup.train_window(step_ends, losses, tokens_per_step,
                               cell.chips)
    roll["setup_s"] = setup_s
    k = max(1, min(5, len(losses) // 2))
    loss_start = sum(losses[:k]) / k if losses else math.nan
    loss_end = sum(losses[-k:]) / k if losses else math.nan
    roll.update(loss_first_step=first_loss, loss_reference=ref_loss,
                loss_window_start=loss_start, loss_window_end=loss_end)
    try:
        peak = peaks_for(devs[0].device_kind)
        roll["mfu"] = (roll["train_tokens_per_s_per_chip"]
                       * model.train_flops_per_token(doc, seq)
                       / peak["bf16_flops_per_s"])
    except KeyError:
        peak = None
        roll["mfu"] = "not measured (no peak for this device)"
    say("window: " + compact(roll, 6))
    dump(dump_path, cell=cell.name, seed=seed, seconds=seconds, roll=roll,
         step_ends=step_ends, losses=losses)

    chk = tr["check"]
    ref_ok = abs(first_loss - ref_loss) <= chk["tol_loss_abs"]
    falls = bool(losses) and loss_end < loss_start
    no_compile = compiled_before == compiled_after
    say(f"checks: |first loss - reference| = {abs(first_loss - ref_loss):.2e}"
        f" (tolerance {chk['tol_loss_abs']}) ok={ref_ok}; loss falls over "
        f"the window={falls}; nothing compiled in the window={no_compile} "
        f"({compiled_before} -> {compiled_after})")
    correct = bool(ref_ok and falls and no_compile and roll["nonfinite"] == 0)
    compared = {
        "first_loss_gap": {"value": abs(first_loss - ref_loss),
                           "limit": chk["tol_loss_abs"]},
        "loss_end_less_start": {"value": (loss_end - loss_start)
                                if losses else None, "limit": 0},
        "nonfinite_losses": {"value": roll["nonfinite"], "limit": 0},
        "compiled_in_window": {"value": int(not no_compile), "limit": 0}}

    stats = [d.memory_stats() or {} for d in devs]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(max(
                  [program_bytes] + [s.get("peak_bytes_in_use", 0)
                                     for s in stats]))}
    breakdown = None
    if trace:
        if traced is None:
            raise CellFailed("the window ended before the traced span began")
        path = trace_lib.find_xplane(trace_dir)
        if path is None:
            raise CellFailed(f"no .xplane.pb under {trace_dir}")
        summary = trace_lib.summarize(trace_lib.load_xplane(path),
                                      traced["t1"] - traced["t0"])
        say("trace: " + compact({k_: summary[k_] for k_ in (
            "window_s", "busy_s", "collective_s", "collective_exposed_s",
            "devices", "programs")}))
        say("trace ops: " + compact(summary["ops"][:20]))
        say("trace idle: " + compact(summary["idle"][:10]))
        if want == "tpu" and not summary["busy_s"] > 0:
            raise CellFailed("the traced span holds no device operation")
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = trace_lib.breakdown(summary)
        ctx = {"cell": cell.entry, "config": doc, "traffic": job,
               "seconds": seconds, "roll": roll, "trace": summary,
               "model": model, "span": {"steps": traced["steps"],
                        "seconds": traced["t1"] - traced["t0"]},
               "step_ends": step_ends, "device": device, "peaks": peak,
               "chips": cell.chips}
        values = {m["name"]: cell.reader(m["name"])(ctx)
                  for m in cell.metrics("per_layer")}
        metrics_out = manifest.metric_line(values, cell.metrics("per_layer"))
    else:
        metrics_out = manifest.metric_line(roll, cell.metrics("end_to_end"))
    result_line(correct, started, roll["nonfinite"], metrics_out, device,
                breakdown, compared)
