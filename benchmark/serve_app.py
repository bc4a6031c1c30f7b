"""The benchmark's deployment class: ``LLMServer`` with a constructor that
builds the model from a configuration file and its block kind's file
(``models/<model_type>.py``, resolved by the parent and loaded here by
path) instead of ``models/config.PRESETS``, which a benchmark PR may not
touch.  Everything a request touches is the program's
own: ``serve.deployment`` / ``serve.run``, the router, the replica actor,
``LLMServer.__call__`` and the unmodified ``LLMEngine``.

The extra methods run only outside requests: warming, the comparison with
the plain reference, the profiler, and reading memory.  They live here
because only the process that holds the chip can do them.  This module
imports no JAX at import time: the parent of a serve cell imports it to name
the class and must stay off the chip.

``ray_tpu.core.serialization`` ships a class defined outside ``ray_tpu/`` to
the workers by value, module globals included.  So nothing here compares
against a module-level singleton of another module (a by-value copy is
another object), and imports are absolute.
"""

from __future__ import annotations

import asyncio
import functools
import json
import os
import time

from ray_tpu.serve.llm import LLMEngine, LLMServer

from benchmark.lib import loadgen, manifest


class BenchLLMServer(LLMServer):

    def __init__(self, config_path: str, model_path: str, seed: int,
                 chips: int):
        import jax
        import jax.numpy as jnp

        t0 = time.monotonic()
        with open(config_path) as f:
            self.doc = json.load(f)
        devs = jax.devices()
        want = self.doc.get("platform", "tpu")
        if devs[0].platform != want or len(devs) != chips:
            raise RuntimeError(
                f"this cell needs {chips} {want} device(s); jax.devices() "
                f"found platform={devs[0].platform} "
                f"kind={devs[0].device_kind!r} count={len(devs)}")
        self.seed = loadgen.fold_seed(seed)
        self.model = model = manifest.load_model(model_path)
        cfg = model.program_config(self.doc)
        dep = self.doc["serve"]
        # the weights: one jitted call from the seed, on the device, in the
        # type they are served in.  The key is an argument: closed over, the
        # seed is a constant of the program and every seed compiles anew.
        params = jax.jit(lambda key: model.init_params(
            key, cfg, jnp.bfloat16))(jax.random.PRNGKey(self.seed))
        jax.block_until_ready(params)
        t1 = time.monotonic()
        self.engine = LLMEngine(
            cfg, params=params, num_slots=dep["num_slots"],
            max_len=dep["max_len"], buckets=tuple(dep["buckets"]),
            seed=self.seed, paged=bool(dep.get("paged", False)),
            **dep.get("engine_kwargs", {}))
        self.timings = {"params_s": t1 - t0,
                        "engine_s": time.monotonic() - t1}
        self._trace = None
        # the replica's default executor is the request path's (one thread
        # per waiting stream): the benchmark's own calls keep off it
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(max_workers=2,
                                        thread_name_prefix="bench")

    # --------------------------------------------------------------- set-up

    def device_info(self) -> dict:
        devs = self.engine._jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "timings": self.timings,
                "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}

    async def warm(self, prompt_lens: list) -> dict:
        """Compile the decode program and one prefill program per length in
        ``prompt_lens``, through the engine's own submit path."""
        def run():
            t0 = time.monotonic()
            for n in prompt_lens:
                self.engine.generate([1] * n, max_tokens=2)
            return time.monotonic() - t0
        seconds = await asyncio.get_event_loop().run_in_executor(
            self._pool, run)
        return {"warm_s": seconds, **self.compile_state()}

    def compile_state(self) -> dict:
        """What would change if anything compiled: the prefill programs the
        engine holds, each jitted program's count of compiled shapes, and
        the files in the persistent cache."""
        from ray_tpu.utils.compile_cache import cache_entries
        eng = self.engine
        fns = {"decode": eng._decode_fn,
               **{f"prefill_{b}": f for b, f in eng._prefill_fns.items()}}
        return {"prefill_buckets": sorted(eng._prefill_fns),
                "compiled_shapes": {k: f._cache_size()
                                    for k, f in sorted(fns.items())},
                "cache_entries": cache_entries(
                    os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))}

    # ----------------------------------------------------------- the tracer

    async def trace_start(self, trace_dir: str) -> dict:
        """The device's operations and the ``TraceAnnotation`` spans, and
        not the Python tracer (on by default): nothing reads its events,
        and its hook on every call in every thread of a replica with some
        tens of open streams delayed their tokens by 0.7 s and made the
        arrivals of a traced window a second late (PERF.md, PR 34)."""
        jax = self.engine._jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        await asyncio.get_event_loop().run_in_executor(
            self._pool, functools.partial(
                jax.profiler.start_trace, trace_dir, profiler_options=options))
        self._trace = {"dir": trace_dir, "t0": time.monotonic(),
                       "stats0": self.stats()}
        return {"t0": self._trace["t0"]}

    async def trace_stop(self) -> dict:
        tr = self._trace
        tr["t1"], tr["stats1"] = time.monotonic(), self.stats()
        await asyncio.get_event_loop().run_in_executor(
            self._pool, self.engine._jax.profiler.stop_trace)
        return {"t1": tr["t1"]}

    async def trace_summary(self) -> dict:
        """Reduce the trace (after the window: parsing holds the GIL)."""
        from benchmark.lib import trace
        tr = self._trace

        def run():
            path = trace.find_xplane(tr["dir"])
            if path is None:
                raise RuntimeError(f"no .xplane.pb under {tr['dir']}")
            return trace.summarize(trace.load_xplane(path),
                                   tr["t1"] - tr["t0"])
        summary = await asyncio.get_event_loop().run_in_executor(
            self._pool, run)
        return {"summary": summary, "t0": tr["t0"], "t1": tr["t1"],
                "stats0": tr["stats0"], "stats1": tr["stats1"]}

    # ----------------------------------------------- correctness and memory

    async def check_reference(self) -> dict:
        return await asyncio.get_event_loop().run_in_executor(
            self._pool, self._check_reference)

    def _check_reference(self) -> dict:
        """Prefill then decode steps through the program's own cache path,
        on the engine's own parameters, against the plain reference's one
        full forward pass over the same tokens: logits, not token ids."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        t0 = time.monotonic()
        chk = self.doc["serve"]["check"]
        n_prompt, n_dec = chk["prompt_len"], chk["decode_steps"]
        cfg, doc, params = self.engine.cfg, self.doc, self.engine.params
        model = self.model
        toks = np.random.default_rng([self.seed, 7]).integers(
            1, cfg.vocab_size, size=n_prompt + n_dec).astype(np.int32)
        pos = jnp.arange(n_prompt - 1, n_prompt + n_dec)
        ref = np.asarray(jax.jit(lambda p, t: model.logits(
            p, t, doc, pos))(params, toks))
        cache_len = -(-(n_prompt + n_dec + 1) // 128) * 128
        cache = model.init_cache(cfg, 1, cache_len, self.engine.compute_dtype)
        cache, lg = jax.jit(lambda p, c, t, ln, sl: model.prefill(
            p, c, t, ln, sl, cfg))(params, cache, toks[None, :n_prompt],
                                   np.array([n_prompt], np.int32),
                                   np.array([0], np.int32))
        got = [np.asarray(lg)[0]]
        step = jax.jit(lambda p, c, t, a: model.decode_step(p, c, t, a, cfg))
        for i in range(n_dec):
            cache, lg = step(params, cache,
                             toks[n_prompt + i:n_prompt + i + 1],
                             np.ones((1,), bool))
            got.append(np.asarray(lg)[0])
        diff = np.stack(got) - ref
        out = {"max_abs_diff": float(np.abs(diff).max()),
               "rms_diff": float(np.sqrt((diff ** 2).mean())),
               "ref_std": float(ref.std()),
               "finite": bool(np.isfinite(np.stack(got)).all()),
               "positions": int(len(got)),
               "seconds": time.monotonic() - t0}
        out["ok"] = bool(out["finite"]
                         and out["max_abs_diff"] <= chk["tol_max_abs"]
                         and out["rms_diff"] <= chk["tol_rms"])
        return out

    async def memory(self) -> dict:
        return await asyncio.get_event_loop().run_in_executor(
            self._pool, self._memory)

    def _memory(self) -> dict:
        """Peak bytes on the chip.  ``peak_bytes_in_use`` misses a program's
        temporaries on this runtime (PR 22), so the compiler's own account
        of each warmed program is added to what the idle engine holds."""
        t0 = time.monotonic()
        eng = self.engine
        stats = [d.memory_stats() or {} for d in eng._jax.devices()]
        programs = {}
        lowered = [("decode", eng._decode_fn,
                    (eng.params, eng.cache, eng._state))]
        for b, fn in sorted(eng._prefill_fns.items()):
            lowered.append((f"prefill_{b}", fn, (
                eng.params, eng.cache, eng._state,
                *eng._admit_arrays([], b, []))))
        for name, fn, args in lowered:
            try:
                m = fn.lower(*args).compile().memory_analysis()
                programs[name] = {"temp": int(m.temp_size_in_bytes),
                                  "arguments": int(m.argument_size_in_bytes),
                                  "output": int(m.output_size_in_bytes),
                                  "alias": int(m.alias_size_in_bytes)}
            except Exception as e:  # noqa: BLE001 — reported, not hidden
                programs[name] = {"error": repr(e)}
        held = max((s.get("bytes_in_use", 0) for s in stats), default=0)
        temp = max((p.get("temp", 0) for p in programs.values()), default=0)
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        return {"memory_peak_bytes": int(max(peak, held + temp)),
                "bytes_in_use": int(held), "peak_bytes_in_use": int(peak),
                "bytes_limit": max((s.get("bytes_limit", 0) for s in stats),
                                   default=0),
                "programs": programs, "seconds": time.monotonic() - t0}
