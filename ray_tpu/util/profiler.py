"""On-demand profiler capture (``raytpu profile --node <id>``).

Two capture modes behind one ``capture()`` entry point:

* **jax.profiler.trace** when the process already runs a non-CPU jax
  backend (a TPU train/serve worker): XLA emits its own profile
  directory (TensorBoard/xprof-loadable), which is strictly richer than
  anything a Python sampler can see.
* **Thread-stack sampling** otherwise: a sibling thread samples
  ``sys._current_frames()`` at ``period_s`` and emits Chrome Trace
  Event Format (``B``/``E`` frame pairs per thread — a flame chart in
  chrome://tracing or Perfetto).  This is the CPU/CI fallback and the
  mode used to profile the node agent itself; it needs no dependencies
  and never touches the accelerator runtime.

The RPC plumbing (node_agent ``handle_profile`` -> worker
``handle_profile``) runs the sampler OFF the event loop (it sleeps for
the whole capture window).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Tuple

# ------------------------------------------------- names a capture shows
#
# What a jax.profiler capture (``raytpu profile --node``, or the
# benchmark's ``--trace 1``) calls the program's own pieces.  The
# benchmark's readers match the program names by substring in the device
# planes' ``XLA Modules`` line (``benchmark/lib/readers.py`` and
# ``benchmark/layer_metrics/*.py``): renaming one here empties their
# metrics, so tests/test_trace_names.py pins them.

#: prefill + sample + merge of one admit batch, one program per bucket
#: (``jit_admit_fn``)
PROGRAM_PREFILL = "admit_fn"
#: ``steps_per_dispatch`` decode steps, dense or paged
PROGRAM_DECODE = "engine_decode"
#: one speculative dispatch (draft, verify, accept)
PROGRAM_SPEC_DECODE = "engine_spec_decode"
#: the draft model's prefill of an admit batch
PROGRAM_DRAFT_PREFILL = "engine_draft_prefill"
#: forward + backward + optimizer update (train_step.py and zero.py)
PROGRAM_TRAIN_STEP = "train_step"
#: every host span of the program starts with this
SPAN_PREFIX = "raytpu:"
#: the phases that partition the LLM engine thread's time, each a host
#: span ``raytpu:engine.<phase>`` and a pair of ``loop_<phase>_s`` /
#: ``loop_<phase>_n`` counters in ``LLMServer.stats()``
ENGINE_PHASES = ("admit", "dispatch", "fetch", "emit", "idle")

_TraceAnnotation = None


def host_span(name: str, **args):
    """A host span ``raytpu:<name>`` in whatever jax.profiler session is
    capturing this process: it lands in the same ``.xplane.pb``, on the
    same clock, as the device's operations.  Outside a session the
    ``with`` is a flag check.  ``args`` become the event's stats; more can
    follow through the returned object's ``set_metadata(**args)``."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(SPAN_PREFIX + name, **args)


def named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` as the program ``jit_<name>``, whatever ``fn`` is
    called (a lambda would be ``jit__lambda_``)."""
    import jax

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


def _jax_tpu_ready() -> bool:
    """True only when jax is ALREADY imported here and sees a non-CPU
    backend — the profiler must never be the thing that initializes an
    accelerator runtime."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        devs = jax.devices()
        return bool(devs) and devs[0].platform != "cpu"
    except Exception:
        return False


def _stack_of(frame) -> List[Tuple[tuple, str]]:
    """Outermost-first [(identity, label)] for one thread's live frame.
    Identity excludes the line number: a loop advancing its own lineno
    must not churn the open/close events every sample."""
    out = []
    f = frame
    while f is not None:
        code = f.f_code
        ident = (code.co_filename, code.co_name)
        label = (f"{code.co_name} "
                 f"({code.co_filename.rsplit('/', 1)[-1]}:{f.f_lineno})")
        out.append((ident, label))
        f = f.f_back
    out.reverse()
    return out


def sample_stacks(duration_s: float, period_s: float = 0.01) -> List[dict]:
    """Sample every OTHER thread's stack for ``duration_s`` and coalesce
    consecutive identical frames into Chrome ``B``/``E`` slice pairs —
    the output loads as a flame chart per thread."""
    me = threading.get_ident()
    pid = os.getpid()
    events: List[dict] = []
    open_stacks: Dict[int, List[Tuple[tuple, str]]] = {}
    named: set = set()
    t_end = time.monotonic() + max(duration_s, period_s)
    while time.monotonic() < t_end:
        now_us = time.time() * 1e6
        frames = sys._current_frames()
        names = {t.ident: t.name for t in threading.enumerate()}
        seen = set()
        for tid, frame in frames.items():
            if tid == me:
                continue
            seen.add(tid)
            if tid not in named:
                named.add(tid)
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": tid,
                               "args": {"name": names.get(tid, str(tid))}})
            stack = _stack_of(frame)
            prev = open_stacks.get(tid, [])
            i = 0
            while (i < len(prev) and i < len(stack)
                   and prev[i][0] == stack[i][0]):
                i += 1
            for j in range(len(prev) - 1, i - 1, -1):
                events.append({"ph": "E", "pid": pid, "tid": tid,
                               "ts": now_us, "name": prev[j][1],
                               "cat": "stack"})
            for j in range(i, len(stack)):
                events.append({"ph": "B", "pid": pid, "tid": tid,
                               "ts": now_us, "name": stack[j][1],
                               "cat": "stack"})
            open_stacks[tid] = stack
        # threads that exited since the last tick: close their slices
        for tid in [t for t in open_stacks if t not in seen]:
            now_us = time.time() * 1e6
            for _ident, label in reversed(open_stacks.pop(tid)):
                events.append({"ph": "E", "pid": pid, "tid": tid,
                               "ts": now_us, "name": label,
                               "cat": "stack"})
        time.sleep(period_s)
    end_us = time.time() * 1e6
    for tid, stack in open_stacks.items():
        for _ident, label in reversed(stack):
            events.append({"ph": "E", "pid": pid, "tid": tid,
                           "ts": end_us, "name": label, "cat": "stack"})
    return events


def capture(duration_s: float, out_dir: str,
            prefer_jax: bool = True) -> Tuple[str, str]:
    """Capture ``duration_s`` of this process; returns (artifact_path,
    mode).  Mode "jax": ``artifact_path`` is the ``jax.profiler.trace``
    output directory; mode "stacks": a chrome-trace JSON file."""
    os.makedirs(out_dir, exist_ok=True)
    stamp = f"{os.getpid()}-{int(time.time())}"
    if prefer_jax and _jax_tpu_ready():
        trace_dir = os.path.join(out_dir, f"jax-trace-{stamp}")
        import jax
        with jax.profiler.trace(trace_dir):
            time.sleep(duration_s)
        return trace_dir, "jax"
    events = sample_stacks(duration_s)
    path = os.path.join(out_dir, f"stacks-{stamp}.trace.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    os.replace(tmp, path)
    return path, "stacks"
