"""Continuous-batching LLM inference for the Serve-equivalent.

SURVEY §2.7 note: the reference snapshot has **no** vLLM-style LLM server —
``@serve.batch`` + streaming are its primitives.  This module is the
first-class TPU-native addition BASELINE.json config #4 calls for.

Architecture (TPU-first):
* The **engine** owns a slot-based KV cache (``models/decode.py``) and runs a
  scheduler loop on a dedicated thread: admit pending prompts into free slots
  via a **bucketed prefill** (prompt padded to the next length bucket — one
  compiled program per bucket, jit cache discipline), then run **one decode
  step for the whole active batch** (single compiled program, static shapes).
  New requests join the decode batch at the next step boundary — continuous
  batching without ever changing a tensor shape.
* Decode emits one token per active slot per step; tokens stream to callers
  through per-request queues, so TTFT ≈ one prefill + scheduling delay, and
  a long generation never blocks a short one (the short one retires early,
  freeing its slot for the next admit).
* Sampling is greedy or temperature/top-k, per request.

* **Paged KV cache** (``paged=True``): block-table pages instead of dense
  ``slots x max_len`` rows (``models/paged_decode.py``) — HBM scales with
  actual request lengths, and identical prompt prefixes share pages
  (prefix caching with refcounts).
* **In-replica tensor parallelism** (``tp=N``): params and KV heads are
  sharded over an N-chip mesh with ``NamedSharding``; the same jitted
  prefill/decode programs run SPMD (XLA inserts the collectives).  Deploy
  with ``num_replicas > 1`` for replica-level data parallelism on top.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from ray_tpu.util.profiler import (ENGINE_PHASES, PROGRAM_DECODE,
                                   PROGRAM_DRAFT_PREFILL, PROGRAM_PREFILL,
                                   PROGRAM_SPEC_DECODE, host_span, named_jit)

from . import observability as obs
from .deployment import deployment as serve_deployment

DEFAULT_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048)
_FLUSH = object()


class GenRequest:
    __slots__ = ("tokens", "max_tokens", "temperature", "top_k", "eos_id",
                 "out", "slot", "generated", "submitted_at", "seen_at",
                 "admitted_at", "emit_times", "retired_at", "held_by",
                 "prefill_attrs", "pages", "prompt_len", "cache_len",
                 "deployment", "trace_ctx", "span_parent", "track")

    def __init__(self, tokens: List[int], max_tokens: int,
                 temperature: float, top_k: int, eos_id: Optional[int]):
        self.tokens = tokens
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.out: "queue.Queue" = queue.Queue()
        self.slot = -1
        self.pages: List[int] = []
        self.generated = 0
        self.prompt_len = len(tokens)
        #: positions of this request the device's cache holds once every
        #: dispatched program has run: the prompt, then one a decode step
        self.cache_len = len(tokens)
        # one monotonic stamp per stage: submit (the caller's thread), the
        # first look of the engine's loop that found the request pending,
        # the dispatch of the admit that carries it, every token's _emit
        # (engine thread; emit_times[0] is the first token's) and _retire.
        # The stage counters difference them; the task-event spans get their
        # wall time from the engine's one offset (LLMEngine._wall).
        self.submitted_at = time.monotonic()
        self.seen_at: Optional[float] = None
        self.admitted_at: Optional[float] = None
        self.emit_times: List[float] = []
        self.retired_at: Optional[float] = None
        #: why the last look that saw the request left it pending: "bucket"
        #: (the admit took another bucket), "batch" (prefill_batch was
        #: full), "slot" (no free slot), "pages" (the page arena was full)
        self.held_by: Optional[str] = None
        #: what stood between the admit's dispatch and the first token, for
        #: the prefill span (LLMEngine._program_done)
        self.prefill_attrs: Dict[str, Any] = {}
        # observability: who/what this request belongs to (the replica's
        # deployment tag + the caller's trace context, captured at submit
        # on the caller's thread)
        self.deployment = "-"
        self.trace_ctx: Optional[tuple] = None
        #: previous stage's span id — batch_wait -> prefill -> decode chain
        self.span_parent: Optional[str] = None
        #: the replica's record of the call that brought the request
        #: (observability.RequestTrack); None for a caller of the engine
        self.track = None


class _Phase:
    """One interval of one phase of the engine thread: a host span in a
    profiler capture and the phase's cumulative seconds and count."""
    __slots__ = ("eng", "name", "span", "t0", "t1")

    def __init__(self, eng: "LLMEngine", name: str, **args):
        self.eng, self.name = eng, name
        self.span = host_span("engine." + name, **args)

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.monotonic()
        self.eng._phase_open = (self.name, self.t0)
        return self.span

    def __exit__(self, *exc):
        self.t1 = now = time.monotonic()
        self.span.__exit__(*exc)
        eng = self.eng
        eng._phase_open = None
        eng.loop_s[self.name] += now - self.t0
        eng.loop_n[self.name] += 1


class _Program:
    """One program the engine thread has dispatched and not yet fetched."""
    __slots__ = ("kind", "seq", "out", "snapshot", "dispatched", "streams",
                 "rows", "chunks", "k", "unbound", "start", "done", "steps")

    def __init__(self, kind: str, out: tuple, dispatched: float,
                 snapshot=None, rows=(), chunks: int = 0, k: int = 0):
        #: "admit", "decode" or "spec"
        self.kind = kind
        #: device arrays the fetch reads back
        self.out = out
        #: host stamp taken once the dispatching call had returned
        self.dispatched = dispatched
        #: a decode or speculative dispatch's {slot: request} as it saw them
        self.snapshot = snapshot
        #: an admit's ``(request, positions walked)`` in row order and the
        #: chunks they made up; a speculative dispatch's window
        self.rows, self.chunks, self.k = rows, chunks, k
        #: ordinal since the engine started and the streams live at the
        #: dispatch, and how long the chip had stood without a program
        #: when it came (LLMEngine._in_flight)
        self.seq = self.streams = 0
        self.unbound = 0.0
        #: its run on the chip as the host knows it (_program_done) and the
        #: decode steps or speculative rounds it holds (an admit: one)
        self.start = self.done = 0.0
        self.steps = 1


#: what a slot is doing, every second of it: taken with no first token yet,
#: decoding, ended on the chip with the host yet to know, free with the
#: request that will take it already submitted, free with nobody asking
SLOT_STATES = ("prefill", "live", "tail", "queued", "unfed")


class _SlotAccount:
    """Every slot-second since the engine started, in one of SLOT_STATES.
    The engine thread books at an admit, a first token and a retire; a
    snapshot closes every slot's open interval at its own instant, so the
    five sums of any snapshot add up to slots x the time since the start.
    One lock, taken at those three events of a request and at a snapshot:
    a reader on another thread never sees an interval booked and open."""

    def __init__(self, num_slots: int):
        self.lock = threading.Lock()
        self.sums = dict.fromkeys(SLOT_STATES, 0.0)
        now = time.monotonic()
        #: slot -> (what it is in now: "free", "prefill" or "live"; since)
        self.open = {s: ("free", now) for s in range(num_slots)}

    def take(self, slot: int, submitted_at: float, now: float) -> float:
        """An admit dispatched at ``now`` takes ``slot`` for a request
        submitted at ``submitted_at``: the vacancy up to the submit was
        unfed (returned), the rest queued."""
        with self.lock:
            since = self.open[slot][1]
            asked = min(max(submitted_at, since), now)
            self.sums["unfed"] += asked - since
            self.sums["queued"] += now - asked
            self.open[slot] = ("prefill", now)
        return asked - since

    def first_token(self, slot: int, now: float):
        with self.lock:
            self.sums["prefill"] += now - self.open[slot][1]
            self.open[slot] = ("live", now)

    def retire(self, slot: int, ended: float, now: float) -> float:
        """The host retires ``slot``'s request at ``now``; on the chip it
        had ended at ``ended``.  Returns the tail between the two."""
        with self.lock:
            since = self.open[slot][1]
            ended = min(max(ended, since), now)
            self.sums["live"] += ended - since
            self.sums["tail"] += now - ended
            self.open[slot] = ("free", now)
        return now - ended

    def snapshot(self, now: float, pending: List[float]) -> dict:
        """The five sums with every open interval closed at ``now``.  A
        request that has ended on the chip and not yet on the host still
        reads live (its tail is booked at the retire); a free slot reads
        queued from the submit of the pending request that will take it
        (``pending``: their submit stamps, oldest first, as slots free
        oldest first), unfed before it and where nobody is pending."""
        with self.lock:
            out, open_ = dict(self.sums), sorted(
                self.open.values(), key=lambda o: o[1])
        waiting = iter(pending)
        for what, since in open_:
            if what != "free":
                out[what] += now - since
                continue
            asked = min(max(next(waiting, now), since), now)
            out["unfed"] += asked - since
            out["queued"] += now - asked
        return {f"slot_{k}_s": v for k, v in out.items()}


class LLMEngine:
    """Slot-scheduled continuous batching over prefill/decode programs."""

    def __init__(self, cfg, params=None, *, num_slots: int = 8,
                 max_len: Optional[int] = None, buckets=DEFAULT_BUCKETS,
                 compute_dtype=None, seed: int = 0, top_k: int = 0,
                 steps_per_dispatch: int = 8,
                 prefill_batch: Optional[int] = None,
                 warmup_buckets: bool = False,
                 paged: bool = False, page_size: int = 64,
                 num_pages: Optional[int] = None, prefix_cache: bool = True,
                 tp: int = 1, spec_decode_enabled: bool = False,
                 spec_k: int = 4, spec_draft_layers: int = 1,
                 spec_adaptive: bool = True):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import decode as dec
        from ray_tpu.models import transformer

        self.cfg = cfg
        if cfg.layer_pattern:
            # a cache of several kinds of state (models/hybrid.py, the
            # rings of models/decode.py) is dense and unsharded, whatever
            # MLP lies under its layers; a recurrent state is decoded one
            # token a step, rows and rings take a verify step's window, and
            # who drafts for it is the model's own block
            recurrent = cfg.recurrent_layers
            for on, what in ((paged, "paged=True: the page arena holds keys "
                              "and values only, in no ring, and no page is "
                              "read by a 'cross' layer or two value heads "
                              "wide"),
                             (spec_decode_enabled and recurrent,
                              "spec_decode_enabled: a "
                              "rejected draft cannot be rolled out of a "
                              "recurrent state ('linear', 'ssm', 'ssm1')"),
                             (spec_decode_enabled and not cfg.mtp_layers,
                              "spec_decode_enabled: no draft model is cut "
                              "out of a pattern's stacks by kind; a pattern "
                              "drafts with its own multi-token-prediction "
                              "block (mtp_layers)"),
                             (tp > 1, f"tp={tp}: no sharding rule covers the "
                              "recurrent state, the rings, the rows a "
                              "'cross' layer shares or their kernels")):
                if on:
                    raise ValueError(
                        f"layer_pattern {cfg.layer_pattern} does not run "
                        f"with {what}")
        if cfg.latent_tree:
            # latent rows, dropless experts and residual streams
            # (models/latent.py) run on a dense cache of their own kind,
            # unsharded, one token a step
            for on, what in ((paged, "paged=True: the page arena holds keys "
                              "and values only, and the latent decode "
                              "kernel reads rows by slot"),
                             (spec_decode_enabled, "spec_decode_enabled: a "
                              "window of several tokens has no latent "
                              "kernel, and the draft would be the dense "
                              "prefix alone"),
                             (tp > 1, f"tp={tp}: no sharding rule covers the "
                              "latent rows, the experts or their kernels")):
                if on:
                    raise ValueError(
                        f"{', '.join(cfg.latent_tree)} do not run with "
                        f"{what}")
        if cfg.share_by_position:
            raise ValueError(
                "share_by_position is the train step's: a served "
                "share holds the experts from expert_start on")
        if cfg.rope_yarn_kinds:
            raise ValueError(
                f"rope_yarn_kinds {cfg.rope_yarn_kinds} is the train "
                "step's (models/transformer.py rope_table): the serving "
                "path's rotary table by kind (models/decode.py _qkv) is "
                "plain")
        self.max_len = max_len or cfg.max_seq_len
        self.num_slots = num_slots
        self.buckets = tuple(b for b in buckets if b <= self.max_len)
        self.compute_dtype = compute_dtype or jnp.bfloat16
        self.top_k = top_k
        # decode steps fused into one dispatch: amortizes the host's
        # per-dispatch cost at the price of <= steps_per_dispatch wasted
        # steps after a sequence finishes.  It is also what an admission
        # waits for: the loop looks at its queue once a pass, as a dispatch
        # starts on the chip, so a request waits up to a pass (a dispatch
        # and the admit behind it) for that look and then that dispatch,
        # which its admit is queued behind (_loop)
        self.steps_per_dispatch = max(1, steps_per_dispatch)
        self._dec = dec
        self._jax = jax
        self._jnp = jnp
        if params is None:
            params = transformer.init_params(
                jax.random.PRNGKey(seed), cfg, dtype=jnp.bfloat16)
        self.params = params
        # A fixed shape, counted rows: admission batches are padded to a
        # FIXED size so each length bucket compiles exactly one prefill
        # program (a varying batch dim would recompile mid-traffic), and
        # that program walks only the rows that hold a request, their
        # count data (decode.prefill).  So prefill_batch is the most rows
        # one admit may take, not a shape the chip pays for.  Padding rows
        # aim at a scratch cache slot (index num_slots) that decode never
        # activates; only their sampling state is ever written there.
        self.prefill_batch = prefill_batch or min(num_slots, 8)
        self._scratch_slot = num_slots
        self.paged = paged
        if paged:
            from ray_tpu.models import paged_decode as pdec
            self.page_size = page_size
            self.max_pages_per_slot = -(-self.max_len // page_size)
            # default HBM budget = half the dense cache (the paged win)
            self.num_pages = num_pages or max(
                (num_slots + 1) * self.max_pages_per_slot // 2, 16)
            self.cache = pdec.init_paged_cache(
                cfg, self.num_pages, page_size, num_slots + 1,
                self.max_pages_per_slot, self.compute_dtype)
            self.allocator = pdec.PageAllocator(self.num_pages)
            self.prefix = (pdec.PrefixCache(self.allocator, page_size)
                           if prefix_cache else None)
        else:
            # a ring has the margin of the verify step's window where the
            # model's own block drafts (one token: a window of two)
            self.cache = dec.init_kv_cache(
                cfg, num_slots + 1, self.max_len, self.compute_dtype,
                ring=dec.ring_len(cfg, 2 if spec_decode_enabled else 1)
                if cfg.window_layers else None)
        # bytes by kind of per-slot state and layers by kind: shapes, so
        # read once (the cache's arrays are donated at every dispatch)
        self._cache_gauges = dec.cache_gauges(cfg, self.cache)
        if not paged:
            from ray_tpu.ops.decode_attention import block_len
            # what a position holds of one layer: a K (or V) row, or its
            # latent row and rotary key
            width = (cfg.latent_row if "latent" in self.cache
                     else self.cache["k"].shape[-1])
            self._kv_block = block_len(
                self.max_len,
                width * jnp.dtype(self.compute_dtype).itemsize)
        # In-replica tensor parallelism: place params + cache with tp
        # shardings; jit propagates them, XLA inserts the collectives.
        self.tp = tp
        self.mesh = None
        if tp > 1:
            if cfg.num_kv_heads % tp:
                raise ValueError(f"tp={tp} must divide num_kv_heads="
                                 f"{cfg.num_kv_heads}")
            from jax.sharding import Mesh
            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(f"tp={tp} but only {len(devs)} devices")
            self.mesh = Mesh(devs[:tp], ("tp",))
            self.params, self.cache = self._apply_tp_sharding(
                self.params, self.cache)
        # Device-resident autoregressive state: token/active/temp/budget/eos
        # per slot plus the PRNG key.  EVERYTHING the scheduler loop touches
        # on the device goes through exactly two jitted programs: an eager
        # op or a small host->device transfer per loop iteration (a
        # per-retire `.at[].set`, a per-dispatch eager `fold_in`) is a
        # separate dispatch and a sync point that the scheduler thread
        # pays between decode programs, idling the chip meanwhile.
        self._state = dec.init_decode_state(num_slots + 1,
                                            jax.random.PRNGKey(seed + 1))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._state = jax.device_put(
                self._state, NamedSharding(self.mesh, P()))

        # Compiled programs: one decode dispatch (cache + state donated —
        # the multi-GB cache must be updated in place, not copied), one
        # prefill per bucket (lazy unless warmup_buckets).
        self._decode_fn = named_jit(
            PROGRAM_DECODE,
            lambda p, c, st: dec.decode_state_loop(
                p, c, st, self.steps_per_dispatch, cfg, top_k,
                self.compute_dtype),
            donate_argnums=(1, 2))
        self._prefill_fns: Dict[int, Any] = {}

        # Speculative decoding (spec_decode_enabled=False => today's path
        # exactly: no draft state exists and _dispatch_step never branches).
        # A layers-sliced draft shares embed/lm_head with the target and
        # keeps a DENSE cache (the paged HBM win matters for the big
        # target); per dispatch the adaptive controller picks k from
        # occupancy — speculation pays when slots are idle, so k shrinks
        # as the batch fills (min k=2 rather than a plain-decode fallback,
        # which would let the draft cache diverge from the target's).
        # A model with a multi-token-prediction block (cfg.mtp_layers)
        # drafts with it instead: one token a round, k fixed at 2 (the
        # step's shape does not follow occupancy), its rows in the target's
        # own cache tree and filled by the target's admit program, so there
        # is no draft model, cache or prefill.
        self.spec_enabled = bool(spec_decode_enabled)
        if self.spec_enabled:
            if tp > 1:
                raise ValueError("spec_decode_enabled does not compose with "
                                 "tp>1 yet (draft params are unsharded)")
            import dataclasses as _dc

            from ray_tpu.models import speculative as spec_mod
            self._spec = spec_mod
            self._spec_self = bool(cfg.mtp_layers)
            d = max(1, min(int(spec_draft_layers), cfg.num_layers - 1))
            self.spec_k = 2 if self._spec_self else max(2, int(spec_k))
            self.spec_adaptive = bool(spec_adaptive) and not self._spec_self
            self.spec_draft_layers = 0 if self._spec_self else d
            if self._spec_self:
                self._drafter = spec_mod.block_drafter(cfg,
                                                       self.compute_dtype)
                self._draft_params, self._draft_cache = {}, {}
            else:
                self._drafter = _dc.replace(cfg, num_layers=d)
                self._draft_params = spec_mod.make_draft_params(
                    self.params, d)
                self._draft_cache = dec.init_kv_cache(
                    self._drafter, num_slots + 1, self.max_len,
                    self.compute_dtype)
            self._spec_fns: Dict[int, Any] = {}
            self._draft_prefill_fns: Dict[int, Any] = {}
            self._spec_ks = sorted({self.spec_k,
                                    max(2, (self.spec_k + 1) // 2), 2},
                                   reverse=True)
            # accounting (breakdown()["spec"] + raytpu_serve_spec_* read
            # these; derived host-side from per-round emit counts only)
            self.spec_rounds = 0
            self.spec_tokens = 0
            self.spec_drafted = 0
            self.spec_accepted = 0
            self.spec_draft_errors = 0
            self.spec_dispatch_k: Dict[int, int] = {}
        else:
            self._spec = None

        # scheduler state
        self._pending: "queue.Queue[GenRequest]" = queue.Queue()
        self._active: Dict[int, GenRequest] = {}
        self._free_slots = list(range(num_slots))
        # dispatched-but-unfetched programs, oldest first
        self._unfetched: List[_Program] = []
        self._stop = False
        self._wake = threading.Event()
        # steady-state metrics
        self.steps = 0
        self.tokens_out = 0
        # admission accounting, of what the chip walked (bench_llm reads
        # these): admits and the rows that held a request (the prefill
        # program walks no other)
        self.admit_batches = 0
        self.admit_rows_real = 0
        # the same per token: prompt tokens prefilled, and every other
        # position the program walked (a row is rounded up to its bucket,
        # or to whole chunks where the program walks it in chunks:
        # decode.prefill_width)
        self.admit_tokens_real = 0
        self.admit_tokens_padded = 0
        # what decode attention reads of the cache it holds (dense cache,
        # plain decode): per step the live positions of the active slots,
        # rounded up to the kernel's blocks, against every slot's max_len;
        # and the same positions as they are, not rounded
        self.kv_positions_read = 0
        self.kv_positions_held = 0
        self.kv_positions_live = 0
        # under a cross-decoder (cfg.cross_segment): prompt tokens taken
        # through the self-decoder (every one) and through the cross-decoder
        # (a row's last alone), and the shared rows a decode step reads:
        # live positions x the layers that read the one full layer's rows
        self.prefill_self_tokens = 0
        self.prefill_cross_tokens = 0
        self.shared_kv_positions_read = 0
        self._shared_kv_readers = (
            cfg.full_layers + cfg.cross_layers
            if cfg.cross_segment else 0)
        # what the dropless expert layers did (cfg.moe_dropless): decode's
        # assignments (live tokens x experts a token x expert layers) and
        # experts touched (those with a live token, summed over expert
        # layers and steps) ride each dispatch's tokens; the steps that had
        # a live slot, times the expert layers, and the admits' assignments
        # are counted here
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        self.moe_expert_layer_steps = 0
        self.moe_assignments_prefill = 0
        # request stages (engine thread): submit -> dispatch of the admit,
        # that dispatch -> first token on the request's queue
        self.admitted_requests = 0
        self.queue_wait_s = 0.0
        self.first_tokens = 0
        self.first_token_wait_s = 0.0
        # the queue wait by cause: submit -> the first look of _loop that
        # found the request pending, that look -> the look that admitted it
        # (looks that saw it and left it: GenRequest.held_by says why);
        # what is left of queue_wait_s is the host building and dispatching
        # the admit
        self.queue_look_s = 0.0
        self.queue_held_s = 0.0
        # the first-token wait by cause (_program_done): the admit's
        # dispatch -> its start on the chip (the programs in flight ahead
        # of it), then the admit's run split by the positions walked for
        # the request's own row over the whole admit's; what is left of
        # first_token_wait_s is the fetch's return to _emit
        self.first_token_ahead_s = 0.0
        self.first_token_own_row_s = 0.0
        self.first_token_other_rows_s = 0.0
        # what holds a stream between its tokens: every program's run
        # times the streams live at its dispatch (requests with a first
        # token, not retired), and the same for the admits alone
        self.stream_s = 0.0
        self.stream_admit_s = 0.0
        # a slot's time by what it was doing (_SlotAccount) and the requests
        # retired; the program whose tokens the emit phase is handing out,
        # the step (or round) of it that made them, from which _retire
        # reads when the request ended on the chip, and the requests that
        # ended in this phase, for its span
        self._slots = _SlotAccount(num_slots)
        self.retired_requests = 0
        self._emitting: Optional[_Program] = None
        self._emit_step = 0
        self._ended: List[str] = []
        # the chip without a program while a stream was live, as the loop
        # sees it at a dispatch (_in_flight), and a decode or speculative
        # program's run as a fetch that had to wait last measured it
        self.chip_unbound_s = 0.0
        self.chip_unbound_n = 0
        self._ran: Dict[tuple, float] = {}
        # the look of the pass under way, programs dispatched since the
        # engine started, and the return of the last blocking fetch: the
        # engine is the chip's only submitter, so a program starts at the
        # later of its dispatch and the end of the one before it
        self._look_at = 0.0
        self._seq = 0
        self._done_at = 0.0
        # where the engine thread's time goes (_Phase): cumulative seconds
        # and intervals per phase, the interval open now, passes of _loop
        self.loop_s = dict.fromkeys(ENGINE_PHASES, 0.0)
        self.loop_n = dict.fromkeys(ENGINE_PHASES, 0)
        self.loop_iterations = 0
        self._phase_open: Optional[tuple] = None
        # monotonic -> wall, taken once: the task-event spans want wall time
        self._wall = time.time() - time.monotonic()
        self._obs_dep = "-"  # deployment tag, learned from first request
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="llm-engine")
        self._thread.start()
        if warmup_buckets:
            for b in self.buckets:
                self.warmup(b)

    # ----------------------------------------------------------- public

    def submit(self, tokens: List[int], max_tokens: int = 64,
               temperature: float = 0.0, top_k: int = 0,
               eos_id: Optional[int] = None, track=None) -> GenRequest:
        """``track``: the replica's record of the call that brings the
        request (``observability.RequestTrack``): it books the call's way
        from the replica's method to this submit and becomes the parent of
        the request's stage spans."""
        if len(tokens) >= self.max_len:
            raise ValueError(f"prompt length {len(tokens)} >= max_len "
                             f"{self.max_len}")
        if self.cfg.cross_segment and len(tokens) > self.buckets[-1]:
            raise ValueError(
                f"prompt length {len(tokens)} is past the largest bucket "
                f"{self.buckets[-1]}: a row of these kinds ('ssm1', "
                "'window', 'cross') is walked whole by one admit program, "
                "its self-decoder over the row (a selective-scan state and "
                "a ring take no start to continue after), and none is "
                "compiled past the buckets")
        req = GenRequest(list(map(int, tokens)), max_tokens, temperature,
                         top_k, eos_id)
        if obs.enabled():
            # caller-thread capture: the replica set both before invoking
            # user code, so engine-side spans/metrics carry the request's
            # deployment tag and chain into its trace
            req.deployment = obs.current_deployment()
            from ray_tpu.util import tracing
            req.trace_ctx = tracing.current_context()
            if req.trace_ctx is None:
                # standalone engine use (no serve request context): mint
                # ONE trace per request so batch_wait -> prefill -> decode
                # still chain together instead of three orphan traces with
                # dangling cross-trace parent links
                req.trace_ctx = (tracing.new_id(), None)
            if req.deployment != "-":
                self._obs_dep = req.deployment
            obs.add_tokens(req.deployment, "in", req.prompt_len)
        if track is not None:
            req.track = track
            req.trace_ctx = track.submitted(req.submitted_at, req.trace_ctx)
        self._pending.put(req)
        self._wake.set()
        return req

    def generate(self, tokens: List[int], **kw) -> List[int]:
        """Blocking convenience: full output token list."""
        return list(self.stream(tokens, **kw))

    def stream(self, tokens: List[int], **kw) -> Iterator[int]:
        req = self.submit(tokens, **kw)
        while True:
            item = req.out.get()
            if item is _FLUSH:
                break
            if isinstance(item, BaseException):
                raise item
            yield item

    def shutdown(self):
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)

    def breakdown(self) -> dict:
        """Serving-picture rollup (bench_llm records this next to the
        per-request percentiles): admit batches, slots in use, KV page
        utilization, prefix-cache hit rate."""
        out = {
            "admit_batches": self.admit_batches,
            "active_slots": len(self._active),
            "num_slots": self.num_slots,
            **self._cache_gauges,
        }
        if self.paged:
            # total = ALLOCATABLE pages (page 0 is the reserved null page),
            # so used/total equals the utilization field
            allocatable = max(self.num_pages - 1, 1)
            out["kv_pages"] = {
                "total": allocatable,
                "used": self.allocator.used(),
                "utilization": self.allocator.used() / allocatable,
            }
            out["prefix_cache"] = (self.prefix.stats()
                                   if self.prefix is not None else None)
        if self.spec_enabled:
            out["spec"] = {
                "k": self.spec_k,
                "draft_layers": self.spec_draft_layers,
                "rounds": self.spec_rounds,
                "tokens": self.spec_tokens,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "acceptance_rate": (self.spec_accepted / self.spec_drafted
                                    if self.spec_drafted else 0.0),
                "rollback_tokens": self.spec_drafted - self.spec_accepted,
                "tokens_per_round": (self.spec_tokens / self.spec_rounds
                                     if self.spec_rounds else 0.0),
                "dispatch_k": dict(self.spec_dispatch_k),
                "draft_errors": self.spec_draft_errors,
            }
        return out

    def counters(self) -> dict:
        """Cumulative counts a reader differences between two calls (always
        on, written by the engine thread alone): admitted tokens, request
        stages, and the engine thread's seconds and intervals per phase.
        The interval open at the call counts up to ``t_mono``, so the five
        ``loop_*_s`` add up to the thread's wall time but for the moments
        between phases, and the five ``slot_*_s`` to ``num_slots`` times
        the time since the engine started (``_SlotAccount.snapshot``)."""
        now = time.monotonic()
        loop_s, open_ = dict(self.loop_s), self._phase_open
        if open_ is not None:
            loop_s[open_[0]] += max(0.0, now - open_[1])
        with self._pending.mutex:
            pending = [r.submitted_at for r in self._pending.queue]
        out = {
            "t_mono": now,
            "loop_iterations": self.loop_iterations,
            "admitted_requests": self.admitted_requests,
            "queue_wait_s": self.queue_wait_s,
            "first_tokens": self.first_tokens,
            "first_token_wait_s": self.first_token_wait_s,
            "queue_look_s": self.queue_look_s,
            "queue_held_s": self.queue_held_s,
            "first_token_ahead_s": self.first_token_ahead_s,
            "first_token_own_row_s": self.first_token_own_row_s,
            "first_token_other_rows_s": self.first_token_other_rows_s,
            "stream_s": self.stream_s,
            "stream_admit_s": self.stream_admit_s,
            "admit_tokens_real": self.admit_tokens_real,
            "admit_tokens_padded": self.admit_tokens_padded,
            "retired_requests": self.retired_requests,
            "chip_unbound_s": self.chip_unbound_s,
            "chip_unbound_n": self.chip_unbound_n,
            **self._slots.snapshot(now, pending),
            "kv_positions_read": self.kv_positions_read,
            "kv_positions_held": self.kv_positions_held,
            "kv_positions_live": self.kv_positions_live,
        }
        if self.cfg.cross_segment:
            out.update(
                prefill_self_tokens=self.prefill_self_tokens,
                prefill_cross_tokens=self.prefill_cross_tokens,
                shared_kv_positions_read=self.shared_kv_positions_read)
        if self.cfg.moe_dropless:
            out.update(
                moe_assignments=self.moe_assignments,
                moe_experts_touched=self.moe_experts_touched,
                moe_expert_layer_steps=self.moe_expert_layer_steps,
                moe_assignments_prefill=self.moe_assignments_prefill)
        if self.spec_enabled:
            # rounds a live slot ran, tokens drafted for them and accepted,
            # and the rows a rejected draft left to be rolled back (one a
            # rejected token, in every kind of cache the model keeps)
            out.update(
                spec_rounds=self.spec_rounds, spec_drafted=self.spec_drafted,
                spec_accepted=self.spec_accepted,
                spec_rolled_back_rows=self.spec_drafted - self.spec_accepted)
        for ph in ENGINE_PHASES:
            out[f"loop_{ph}_s"] = loop_s[ph]
            out[f"loop_{ph}_n"] = self.loop_n[ph]
        return out

    def prefix_digest(self, cap: int = 32) -> Optional[dict]:
        """Bounded digest of this engine's hot first-page prefix chunks
        for cache-aware routing: ``{"page": page_size, "blocks": [8-hex
        truncated chunk hashes]}``.  None when the engine is dense or
        prefix caching is off — the router falls back to pure p2c."""
        if not self.paged or self.prefix is None:
            return None
        return {"page": self.page_size,
                "blocks": self.prefix.first_page_digest(cap)}

    def warmup(self, bucket: Optional[int] = None):
        """Compile prefill(bucket)+decode ahead of traffic."""
        b = bucket or self.buckets[0]
        req = self.submit([1] * min(4, b), max_tokens=2)
        while req.out.get() is not _FLUSH:
            pass

    # -------------------------------------------------------- tp sharding

    def _apply_tp_sharding(self, params, cache):
        """Place params + cache on the tp mesh: attention/MLP weights split
        megatron-style (column then row), KV heads split across chips,
        small/control tensors replicated.  jit then runs the unchanged
        programs SPMD (scaling-book recipe: annotate, let XLA do the rest)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh

        def spec_for(path: str, arr) -> "P":
            dims = arr.ndim

            def at(axis):  # PartitionSpec with 'tp' at `axis`
                parts = [None] * dims
                parts[axis] = "tp"
                return P(*parts)

            # stacked block params carry a leading L dim (scan over layers)
            if "wq" in path or "wk" in path or "wv" in path \
                    or "w_in" in path or "w_gate" in path:
                return at(dims - 1)          # column parallel
            if "wo" in path or "w_out" in path:
                return at(dims - 2)          # row parallel
            if "bq" in path or "bk" in path or "bv" in path \
                    or "b_in" in path:
                return at(dims - 1)
            if path.endswith("/k") or path.endswith("/v"):
                # dense [L, S, len, NKV * D], paged [L, P, page, NKV, D]:
                # whole KV heads a chip either way
                return at(3)
            return P()                       # replicate

        def place(tree):
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            placed = []
            for keypath, leaf in flat:
                path = "/".join(str(getattr(k, "key", k)) for k in keypath)
                placed.append(jax.device_put(
                    leaf, NamedSharding(mesh, spec_for("/" + path, leaf))))
            return jax.tree_util.tree_unflatten(treedef, placed)

        return place(params), place(cache)

    # ----------------------------------------------------- observability

    def _admit_walk(self, reqs: List[GenRequest], bucket: int):
        """(chunks, positions a row) the admit program walks for ``reqs`` at
        ``bucket``: whole rows and no chunks, or the chunks each prompt
        fills (``decode.prefill_width``: a chunk's length is the tree's and
        the experts'; only a tree of rows alone is walked in chunks, and
        its rows are whole prompts)."""
        width = self._dec.prefill_width(self.cache, bucket, self.cfg)
        if width == bucket:
            return 0, [bucket] * len(reqs)
        chunks = [-(-len(r.tokens) // width) for r in reqs]
        return sum(chunks), [c * width for c in chunks]

    def _obs_admit(self, prog: _Program, tokens_real: int):
        """One admit batch, just dispatched: padding accounting (rows and
        tokens), queue wait per request and its two causes, the vacancy of
        the slot it takes (``unfed_s``: free before the request asked);
        then, behind
        one enabled() check, occupancy + queue-wait metrics, batch_wait
        span per request (chained under the request's trace), KV/slot
        gauges.  Engine-thread side; every metric call is a
        precomputed-key observe."""
        now, look = prog.dispatched, self._look_at
        reqs = [r for r, _n in prog.rows]
        self.admit_batches += 1
        self.admit_rows_real += len(reqs)
        self.admit_tokens_real += tokens_real
        self.admit_tokens_padded += sum(n for _r, n in prog.rows) - tokens_real
        if self._shared_kv_readers:
            self.prefill_self_tokens += tokens_real
            self.prefill_cross_tokens += len(reqs)
        # (of a share of the experts, the part that lands on those held
        # under uniform routing: the admit program returns no count)
        self.moe_assignments_prefill += (
            tokens_real * self.cfg.experts_per_token * self.cfg.expert_layers
            * self.cfg.experts_held // self.cfg.num_experts)
        self.admitted_requests += len(reqs)
        causes = []
        for r in reqs:
            r.admitted_at = now
            self.queue_wait_s += now - r.submitted_at
            if r.seen_at is None:
                # submitted after this pass's look and taken all the same:
                # no look made it wait
                r.seen_at = r.submitted_at
            look_s = r.seen_at - r.submitted_at
            held_s = max(0.0, look - r.seen_at)
            self.queue_look_s += look_s
            self.queue_held_s += held_s
            causes.append((look_s, held_s,
                           self._slots.take(r.slot, r.submitted_at, now)))
        if not obs.enabled():
            return
        dep = self._obs_dep
        obs.record_batch(dep, len(reqs), self.prefill_batch,
                         waits_s=[now - r.submitted_at for r in reqs])
        self._obs_gauges()
        for r, (look_s, held_s, unfed_s) in zip(reqs, causes):
            r.span_parent = obs.stamp_span(
                "batch_wait", self._wall + r.submitted_at,
                now - r.submitted_at,
                trace_id=r.trace_ctx[0] if r.trace_ctx else None,
                parent_id=r.trace_ctx[1] if r.trace_ctx else None,
                deployment=r.deployment, look_s=look_s, held_s=held_s,
                held_by=r.held_by, unfed_s=unfed_s)

    def _obs_first_token(self, r: GenRequest, now: float):
        """One request's first token is being emitted (``now``): the stage
        counter; then engine-level TTFT (the rolling SLO window takes
        the replica-level sample instead — one per request) + the
        ``prefill`` span, chained under batch_wait."""
        self.first_tokens += 1
        self.first_token_wait_s += now - r.admitted_at
        self._slots.first_token(r.slot, now)
        if not obs.enabled():
            return
        obs.observe_ttft(r.deployment, now - r.submitted_at,
                         stage="engine", window=False)
        r.span_parent = obs.stamp_span(
            "prefill", self._wall + r.admitted_at, now - r.admitted_at,
            trace_id=r.trace_ctx[0] if r.trace_ctx else None,
            parent_id=r.span_parent,
            deployment=r.deployment, prompt_len=r.prompt_len,
            **r.prefill_attrs)

    def _obs_retire(self, r: GenRequest, tail_s: float):
        """Generation done: decode span (first token -> last; ``tail_s``:
        how long the request had been over on the chip when the host
        retired it), TPOT, token counters, refreshed slot/KV gauges.  A
        buffered stream's span is the replica's to stamp, once the caller
        has taken the stream's end (``RequestTrack.decode_span``)."""
        if not obs.enabled():
            return
        obs.add_tokens(r.deployment, "out", r.generated)
        first, last = r.emit_times[0], r.emit_times[-1]
        span = dict(
            name="decode", t0=self._wall + first, dur=last - first,
            trace_id=r.trace_ctx[0] if r.trace_ctx else None,
            parent_id=r.span_parent,
            deployment=r.deployment, tokens=r.generated, tail_s=tail_s)
        if r.track is not None and r.track.buffered:
            r.track.decode_span = span
        else:
            obs.stamp_span(**span)
        if r.generated > 1:
            obs.observe_tpot(r.deployment, (last - first) / (r.generated - 1))
        self._obs_gauges()

    def _obs_gauges(self):
        obs.set_engine_gauges(
            self._obs_dep, len(self._active),
            kv_pages_used=self.allocator.used() if self.paged else None,
            kv_pages_total=(max(self.num_pages - 1, 1) if self.paged
                            else None))

    # -------------------------------------------------------- scheduler

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_len

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            cfg, dt, tk = self.cfg, self.compute_dtype, self.top_k
            dec = self._dec

            # Prefill + sample + merge into the decode state in ONE
            # fixed-shape program (a varying admit count would compile a
            # fresh program per batch size) that walks counted rows: how
            # many hold a request it reads off real_mask, so an admit of
            # one costs one row.  Admit batches arrive as plain numpy
            # arrays — transferred as part of the async dispatch, not as
            # per-array eager round trips.  Padding rows target the scratch
            # slot.  A paged admit brings two arrays more (start positions,
            # block-table rows): data, not another program.
            def admit_fn(p, c, st, t, ln, sl, tmp, bud, eos, real_mask,
                         *paged):
                return dec.prefill_admit(p, c, st, t, ln, sl, tmp, bud, eos,
                                         real_mask, cfg, tk, dt, *paged)

            fn = named_jit(PROGRAM_PREFILL, admit_fn, donate_argnums=(1, 2))
            self._prefill_fns[bucket] = fn
        return fn

    # ------------------------------------------------- speculative decode

    def _draft_prefill_fn(self, bucket: int):
        """Draft-cache prefill (KV only, logits discarded): the draft has
        no prefix cache, so it always ingests the FULL prompt from
        position 0 — one small compiled program per length bucket."""
        fn = self._draft_prefill_fns.get(bucket)
        if fn is None:
            dcfg, dt = self._drafter, self.compute_dtype
            dec = self._dec

            def f(p, c, t, ln, sl, n):
                return dec.prefill(p, c, t, ln, sl, dcfg, dt, rows=n)[0]

            fn = named_jit(PROGRAM_DRAFT_PREFILL, f, donate_argnums=(1,))
            self._draft_prefill_fns[bucket] = fn
        return fn

    def _draft_prefill(self, reqs: List[GenRequest], slots: List[int]):
        """Ingest the admitted prompts into the draft cache.  Failure here
        never fails the requests: greedy acceptance keeps the OUTPUT exact
        even with a garbage draft (acceptance just collapses), so degrade
        and count instead of unwinding a half-done admit."""
        import numpy as np
        bucket = self._bucket_for(max(len(r.tokens) for r in reqs))
        n_pad = self.prefill_batch - len(reqs)
        toks = np.zeros((self.prefill_batch, bucket), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :len(r.tokens)] = r.tokens
        lengths = np.asarray([len(r.tokens) for r in reqs] + [1] * n_pad,
                             np.int32)
        slots_arr = np.asarray(slots + [self._scratch_slot] * n_pad,
                               np.int32)
        try:
            self._draft_cache = self._draft_prefill_fn(bucket)(
                self._draft_params, self._draft_cache, toks, lengths,
                slots_arr, np.int32(len(reqs)))
            self._seq += 1   # a program of the chip's, though never fetched
        except BaseException:  # noqa: BLE001
            self.spec_draft_errors += 1

    def _spec_k_now(self) -> int:
        """Adaptive k: speculation pays when slots are idle (the verify
        matmul rides free on weight traffic the batch already pays for),
        so shrink the window as occupancy rises.  Never falls back to the
        plain decode program — that would stop feeding the draft cache
        and strand its KV behind the target's for every in-flight
        request."""
        if not self.spec_adaptive or len(self._spec_ks) == 1:
            return self.spec_k
        occ = len(self._active) / max(1, self.num_slots)
        if occ <= 0.5:
            return self._spec_ks[0]
        if occ <= 0.85:
            return self._spec_ks[min(1, len(self._spec_ks) - 1)]
        return self._spec_ks[-1]

    def _spec_fn(self, k: int):
        """One compiled spec-decode program per window size k (static
        shapes; rounds chosen so a dispatch emits at most about
        steps_per_dispatch tokens per slot, matching the plain path's
        readback cadence)."""
        ent = self._spec_fns.get(k)
        if ent is None:
            rounds = max(1, self.steps_per_dispatch // k)
            spec, cfg, dcfg = self._spec, self.cfg, self._drafter
            tk, dt = self.top_k, self.compute_dtype

            def run(tp, tc, dp, dc, st):
                return spec.spec_decode_state_loop(
                    tp, tc, dp, dc, st, k, rounds, cfg, dcfg, tk, dt)

            ent = (named_jit(PROGRAM_SPEC_DECODE, run,
                             donate_argnums=(1, 3, 4)), rounds)
            self._spec_fns[k] = ent
        return ent

    def _run(self):
        """The engine thread.  With ``tp > 1`` its programs are traced under
        the engine's mesh: a dispatch that would hand the compiler a Pallas
        kernel to partition sees it there and keeps to its twin
        (``ops/decode_attention.py``)."""
        if self.mesh is None:
            return self._loop()
        with self._jax.set_mesh(self.mesh):
            return self._loop()

    def _loop(self):
        # Every stretch of this thread's time belongs to one of five phases
        # (_Phase: admit, dispatch, fetch, emit, idle), one span and one
        # counted interval per stretch, never one per token.
        while not self._stop:
            self.loop_iterations += 1
            did_work = False
            keep = 1            # programs left in flight at the next look
            pending = not self._pending.empty()
            if pending:
                self._look()
            # admit: batch pending prompts of the same bucket into one prefill
            if self._free_slots and pending:
                with _Phase(self, "admit") as span:
                    admits: List[GenRequest] = []
                    bucket = None
                    while (len(admits) < len(self._free_slots)
                           and len(admits) < self.prefill_batch
                           and not self._pending.empty()):
                        nxt = self._pending.queue[0]
                        b = self._bucket_for(len(nxt.tokens))
                        if bucket is None:
                            bucket = b
                        if b != bucket:
                            break
                        admits.append(self._pending.get())
                    self._hold(
                        "slot" if len(admits) >= len(self._free_slots)
                        else "batch" if len(admits) >= self.prefill_batch
                        else "bucket")
                    span.set_metadata(
                        bucket=bucket, rows=len(admits),
                        chunks=self._admit_walk(admits, bucket)[0],
                        ahead=len(self._unfetched))
                    self._admit(admits, bucket)
                    self._say_unbound(span)
                    if 2 * len(admits) > self.prefill_batch:
                        keep = 2    # a burst's admit: see the fetch below
                did_work = True
            elif pending:
                self._hold("slot")
            if self._active:
                with _Phase(self, "dispatch") as span:
                    self._dispatch_step()
                    self._say_unbound(span)
                did_work = True
            # Fetch all but the program dispatched last, the decode dispatch
            # behind this pass's admit: when the fetch before it returns the
            # chip has just started on it, so the next pass looks at the
            # queue and binds its admit ONE program ahead of the chip.  One
            # keeps the chip fed (the host's share of a pass is a few
            # milliseconds of a whole dispatch, and an admit, the one program
            # that can be shorter, always has a dispatch behind it); a second
            # would stand between every admit and its first tokens.
            # Behind a burst's admit, one more than half full, the loop
            # stops a fetch sooner and looks as that admit STARTS: what
            # arrives while it runs would be a second long admit, and bound
            # at its end the two hold every live stream with one dispatch
            # between them (PERF.md section 6, PR 43).  With no stream live
            # nothing is held back.
            while len(self._unfetched) > (keep if self._active else 0):
                self._drain_one()
                did_work = True
            if not did_work:
                with _Phase(self, "idle"):
                    self._wake.wait(timeout=0.02)
                    self._wake.clear()

    def _say_unbound(self, span):
        """``unbound_s`` on the phase's span where the program it has just
        dispatched found the chip standing (``_in_flight``)."""
        last = self._unfetched[-1] if self._unfetched else None
        if last is not None and last.unbound and last.seq == self._seq:
            span.set_metadata(unbound_s=last.unbound)

    def _look(self):
        """One look at the queue, at the top of a pass that finds anything
        pending: the stamp, and ``seen_at`` for what it finds for the first
        time."""
        self._look_at = now = time.monotonic()
        with self._pending.mutex:
            for r in self._pending.queue:
                if r.seen_at is None:
                    r.seen_at = now

    def _hold(self, why: str):
        """The pass's look leaves what it saw and did not take: the reason
        goes on each request (the last one rides its batch_wait span)."""
        with self._pending.mutex:
            for r in self._pending.queue:
                if r.seen_at is not None:
                    r.held_by = why

    def _admit_arrays(self, reqs: List[GenRequest], bucket: int,
                      slots: List[int], starts: Optional[List[int]] = None):
        """Build one admit batch as plain numpy arrays (no device ops)."""
        import numpy as np
        n_pad = self.prefill_batch - len(reqs)
        starts = starts or [0] * len(reqs)
        rows = [r.tokens[st:] for r, st in zip(reqs, starts)]
        toks = np.zeros((self.prefill_batch, bucket), np.int32)
        for i, row in enumerate(rows):
            toks[i, :len(row)] = row
        lengths = np.asarray([len(row) for row in rows] + [1] * n_pad,
                             np.int32)
        slots_arr = np.asarray(slots + [self._scratch_slot] * n_pad,
                               np.int32)
        temps = np.asarray([r.temperature for r in reqs] + [0.0] * n_pad,
                           np.float32)
        # effective budget mirrors the host retire predicate:
        # min(max_tokens, room left before max_len)
        budgets = np.asarray(
            [min(r.max_tokens, self.max_len - len(r.tokens)) for r in reqs]
            + [1] * n_pad, np.int32)
        eos = np.asarray(
            [-1 if r.eos_id is None else int(r.eos_id) for r in reqs]
            + [-1] * n_pad, np.int32)
        real_mask = np.asarray([True] * len(reqs) + [False] * n_pad)
        return toks, lengths, slots_arr, temps, budgets, eos, real_mask

    def _admit(self, reqs: List[GenRequest], bucket: int):
        if self.paged:
            self._admit_paged(reqs, bucket)
            return
        slots = [self._free_slots.pop(0) for _ in reqs]
        (toks, lengths, slots_arr, temps, budgets, eos,
         real_mask) = self._admit_arrays(reqs, bucket, slots)
        try:
            self.cache, self._state, first = self._prefill_fn(bucket)(
                self.params, self.cache, self._state, toks, lengths,
                slots_arr, temps, budgets, eos, real_mask)
        except BaseException as e:  # noqa: BLE001
            for r, s in zip(reqs, slots):
                self._free_slots.append(s)
                r.out.put(e)
                r.out.put(_FLUSH)
            return
        self._admitted(reqs, slots, first, bucket,
                       int(lengths[:len(reqs)].sum()))

    def _admitted(self, reqs: List[GenRequest], slots: List[int], first,
                  bucket: int, tokens_real: int):
        """What every admit does once its program is dispatched, dense or
        paged: the rows become active, the draft ingests them, the program
        joins those in flight with what its fetch will account by."""
        for r, s in zip(reqs, slots):
            r.slot = s
            self._active[s] = r
        if self._spec is not None and not self._spec_self:
            self._draft_prefill(reqs, slots)
        self.steps += 1
        chunks, walk = self._admit_walk(reqs, bucket)
        prog = _Program("admit", (first,), time.monotonic(),
                        rows=list(zip(reqs, walk)), chunks=chunks)
        self._obs_admit(prog, tokens_real)
        self._in_flight(prog)

    def _in_flight(self, prog: _Program):
        """``prog`` was just dispatched: its ordinal, and the streams that
        stand still while it runs (requests with a first token, not
        retired, as the host knows them)."""
        self._seq += 1
        prog.seq = self._seq
        prog.streams = sum(1 for r in self._active.values() if r.emit_times)
        if prog.streams:
            self._count_unbound(prog)
        self._unfetched.append(prog)

    def _count_unbound(self, prog: _Program):
        """Did ``prog``, dispatched with a stream live, find the chip
        without a program, and since when?  With nothing in flight the
        host has seen the last program end (``_done_at``): the chip has
        stood since.  With one decode or speculative program in flight
        whose output is there already the chip stands too, since that
        program's start plus its run as a fetch that had to wait last
        measured it (an estimate: the host never saw this one end).  In
        the loop's steady state neither holds: the program in flight is
        still running when the next is bound behind it."""
        if not self._unfetched:
            since = self._done_at
        elif len(self._unfetched) == 1:
            last = self._unfetched[0]
            ran = self._ran.get((last.kind, last.k))
            if ran is None or not last.out[0].is_ready():
                return
            since = max(last.dispatched, self._done_at) + ran
        else:
            return
        if prog.dispatched > since:
            prog.unbound = prog.dispatched - since
            self.chip_unbound_s += prog.unbound
            self.chip_unbound_n += 1

    def _program_done(self, prog: _Program, done: float, waited: float):
        """``prog``'s blocking fetch returned at ``done`` after ``waited``
        seconds.  With a stream
        live the loop has bound what follows ``prog`` before it comes here
        (``_loop`` leaves one program in flight at its look, two behind a
        burst's admit), and comes here within the host's few milliseconds a
        pass of the fetch before: at a busy chip the engine thread is always
        waiting there.  So that is the program's end, and the later of its
        dispatch and the end of the one before it is its start (the engine
        is the chip's only submitter; a speculative engine's draft prefill,
        never fetched, counts into the program after it).  From the two:
        how long the live streams stood behind the program, and for an
        admit what stood between its dispatch and each first token."""
        start = max(prog.dispatched, self._done_at)
        self._done_at = done
        ran = done - start
        prog.start, prog.done = start, done
        self.stream_s += prog.streams * ran
        if prog.kind != "admit":
            if waited > 1e-3:
                # the fetch stood waiting: the host saw the program end
                self._ran[prog.kind, prog.k] = ran
            return
        self.stream_admit_s += prog.streams * ran
        walked = sum(n for _r, n in prog.rows)
        for r, n in prog.rows:
            ahead, own = start - r.admitted_at, ran * n / walked
            self.first_token_ahead_s += ahead
            self.first_token_own_row_s += own
            self.first_token_other_rows_s += ran - own
            r.prefill_attrs = {"ahead_s": ahead, "own_row_s": own,
                               "rows": len(prog.rows),
                               "chunks": prog.chunks}

    def _plan_pages(self, r: GenRequest):
        """Reserve pages for one request: reuse cached prefix pages, allocate
        private pages for the rest of prompt + generation budget.  Returns
        (reused_tokens, page_row) or None when the arena is full."""
        page = self.page_size
        total = min(len(r.tokens) + r.max_tokens + 1, self.max_len)
        reused, rpages = 0, []
        if self.prefix is not None:
            # always leave >= 1 prompt token for the prefill (logits
            # needed) — capped inside the lookup so the counters below
            # match the reuse actually granted
            reused, rpages = self.prefix.match_prefix(
                r.tokens, max_pages=(len(r.tokens) - 1) // page)
        need = -(-total // page) - len(rpages)
        private = self.allocator.alloc(need)
        if private is None and self.prefix is not None:
            self.prefix.evict_some(need * 2)
            private = self.allocator.alloc(need)
        if private is None:
            self.allocator.release(rpages)
            return None
        if self.prefix is not None:
            # counted only on a SUCCESSFUL plan: an arena-full requeue
            # retries this whole function and must not double-count
            self.prefix.count_lookup(reused)
            obs.record_prefix_lookup(r.deployment, reused > 0, reused)
        return reused, rpages + private

    def _admit_paged(self, reqs: List[GenRequest], bucket: int):
        import numpy as np
        planned = []
        for r in reqs:
            plan = self._plan_pages(r)
            if plan is None:
                # arena full: requeue and stop admitting (backpressure)
                r.held_by = "pages"
                self._pending.put(r)
                continue
            planned.append((r, plan))
        if not planned:
            return
        # suffix bucket: longest uncached suffix, padded
        sbucket = self._bucket_for(max(
            len(r.tokens) - reused for r, (reused, _pages) in planned))
        n_pad = self.prefill_batch - len(planned)
        preqs = [r for r, _plan in planned]
        slots = [self._free_slots.pop(0) for _ in planned]
        starts = [reused for _r, (reused, _pages) in planned]
        bt_rows = np.zeros((self.prefill_batch, self.max_pages_per_slot),
                           np.int32)
        for i, (r, (_reused, pages)) in enumerate(planned):
            r.pages = pages
            bt_rows[i, :len(pages)] = pages[:self.max_pages_per_slot]
        (toks, lengths, slots_arr, temps, budgets, eos,
         real_mask) = self._admit_arrays(preqs, sbucket, slots, starts)
        starts_arr = np.asarray(starts + [0] * n_pad, np.int32)
        try:
            self.cache, self._state, first = self._prefill_fn(sbucket)(
                self.params, self.cache, self._state, toks, lengths,
                slots_arr, temps, budgets, eos, real_mask, starts_arr,
                bt_rows)
        except BaseException as e:  # noqa: BLE001
            for (r, (_reused, pages)), s in zip(planned, slots):
                self._free_slots.append(s)
                self.allocator.release(pages)
                r.out.put(e)
                r.out.put(_FLUSH)
            return
        if self.prefix is not None:
            for r in preqs:
                # register this prompt's full pages for future reuse
                self.prefix.insert(r.tokens,
                                   r.pages[:len(r.tokens) // self.page_size])
        # tokens prefilled: each prompt's uncached suffix (after `start`)
        self._admitted(preqs, slots, first, sbucket,
                       int(lengths[:len(preqs)].sum()))

    def _dispatch_step(self):
        if self._spec is not None:
            k = self._spec_k_now()
            fn, rounds = self._spec_fn(k)
            res = fn(self.params, self.cache, self._draft_params,
                     self._draft_cache, self._state)
            self.cache = res["target_cache"]
            self._draft_cache = res["draft_cache"]
            self._state = res["state"]
            self._in_flight(_Program(
                "spec", (res["tokens"], res["counts"], res["emit_counts"])
                + ((res["moe_counts"],) if "moe_counts" in res else ()),
                time.monotonic(), dict(self._active), k=k))
            self.steps += rounds
            self.spec_dispatch_k[k] = self.spec_dispatch_k.get(k, 0) + 1
            return
        self.cache, self._state, emitted = self._decode_fn(
            self.params, self.cache, self._state)
        self._in_flight(_Program("decode", (emitted,), time.monotonic(),
                                 dict(self._active)))
        self.steps += self.steps_per_dispatch
        if not self.paged:
            self._count_kv_positions()

    def _count_kv_positions(self):
        """One decode dispatch's ``kv_positions_read`` / ``_held`` / ``_live``.  The
        device runs a slot for as many steps as its budget has left
        (``_admit_arrays``; an EOS it samples is not known here yet) and
        reads, at a step that finds ``n`` positions cached, the blocks that
        hold ``n + 1``."""
        steps, block = self.steps_per_dispatch, self._kv_block
        longest = 0
        for r in self._active.values():
            budget = min(r.max_tokens, self.max_len - r.prompt_len)
            run = min(steps, budget - 1 - (r.cache_len - r.prompt_len))
            self.kv_positions_read += sum(
                -(-(r.cache_len + j + 1) // block) * block
                for j in range(run))
            if run > 0:
                live = run * r.cache_len + run * (run + 1) // 2
                self.kv_positions_live += live
                self.shared_kv_positions_read += live * self._shared_kv_readers
            r.cache_len += max(run, 0)
            longest = max(longest, run)
        self.kv_positions_held += steps * (self.num_slots + 1) * self.max_len
        # the steps of this dispatch that find a live slot
        self.moe_expert_layer_steps += longest * self.cfg.expert_layers

    def _emit_spec(self, tokens, rounds, k: int, snapshot):
        """Emit each slot's accepted window (``tokens[s]``: what its rounds
        emitted, ``rounds[:, s]`` of them each) and fold the per-round emit
        counts into the acceptance tallies (a round's emit_count e in 1..k
        means e-1 drafts accepted + one verified correction; the k-1-e
        rejected drafts are the rollback)."""
        import numpy as np
        d_tok = d_round = d_draft = d_acc = 0
        for row in rounds:
            act = int((row > 0).sum())
            if not act:
                continue
            d_round += act
            d_tok += int(row.sum())
            d_draft += (k - 1) * act
            d_acc += int(np.minimum(np.maximum(row - 1, 0), k - 1).sum())
        self.spec_rounds += d_round
        self.spec_tokens += d_tok
        self.spec_drafted += d_draft
        self.spec_accepted += d_acc
        # the rounds that found a live slot, times the expert layers (the
        # drafting block's among them): what decode's experts' counts are
        # set beside
        self.moe_expert_layer_steps += sum(
            bool((row > 0).any()) for row in rounds) * (
                self.cfg.expert_layers + (self.cfg.mtp_layers
                                          if self._spec_self else 0))
        if d_round:
            obs.record_spec_dispatch(self._obs_dep, d_round, d_tok,
                                     d_draft, d_acc)
        # a slot's window holds what its rounds emitted, in their order:
        # walked round by round, so that a request that ends knows the
        # round that made its last token (_retire)
        for s, r in snapshot.items():
            if r.slot != s or self._active.get(s) is not r:
                continue
            window = iter(tokens[s])
            for self._emit_step, row in enumerate(rounds):
                for _ in range(int(row[s])):
                    if self._active.get(s) is not r:
                        break
                    self._emit(r, int(next(window)))

    def _drain_one(self):
        """Fetch the oldest program in flight, then emit what it made."""
        import numpy as np
        prog = self._unfetched.pop(0)
        fetch = _Phase(self, "fetch", program=prog.kind, seq=prog.seq)
        with fetch:
            # blocks until the program has run
            fetched = [np.asarray(a) for a in prog.out]
        self._program_done(prog, fetch.t1, fetch.t1 - fetch.t0)
        with _Phase(self, "emit") as span:
            before = self.tokens_out
            self._emitting, self._emit_step, self._ended = prog, 0, []
            if prog.kind == "spec":
                # rounds [num_rounds, slots]; the experts' counts where the
                # model has them
                tokens, _counts, rounds, *moe = fetched
                if moe:
                    self.moe_assignments += int(moe[0][0])
                    self.moe_experts_touched += int(moe[0][1])
                prog.steps = len(rounds)
                self._emit_spec(tokens, rounds, prog.k, prog.snapshot)
            elif prog.kind == "admit":
                # tokens is [prefill_batch], the real rows first
                for (r, _n), token in zip(prog.rows, fetched[0]):
                    self._emit(r, int(token))
            else:
                # [steps_per_dispatch, slots], and a row of the expert
                # layers' counts where the model has them
                tokens = fetched[0]
                if self.cfg.moe_dropless:
                    tokens, (ran, touched) = self._dec.split_moe_counts(
                        tokens)
                    self.moe_assignments += ran
                    self.moe_experts_touched += touched
                prog.steps = tokens.shape[0]
                for k in range(prog.steps):
                    self._emit_step = k
                    for s, r in prog.snapshot.items():
                        if r.slot == s and self._active.get(s) is r:
                            self._emit(r, int(tokens[k, s]))
            span.set_metadata(tokens=self.tokens_out - before)
            if self._ended:
                # the requests that ended with this program, each as
                # "step:ms before the fetch's return": the engine's estimate
                # of its end on the chip, to set beside the device's own
                # event of the program the fetch span before this one names
                span.set_metadata(ended=",".join(self._ended))

    def _emit(self, r: GenRequest, token: int):
        r.tokens.append(token)
        r.generated += 1
        self.tokens_out += 1
        now = time.monotonic()
        if not r.emit_times:
            self._obs_first_token(r, now)
        # stamped before the put: the consumer that got token n finds
        # emit_times[n] (LLMServer.__call__ reads its deliver lag from it)
        r.emit_times.append(now)
        r.out.put(token)
        done = (r.generated >= r.max_tokens
                or (r.eos_id is not None and token == r.eos_id)
                or len(r.tokens) >= self.max_len)
        if done:
            self._retire(r)

    def _retire(self, r: GenRequest):
        # No device write: the decode program decays `active` on device by
        # the same budget/EOS predicate the host applies in _emit, so the
        # device copy is already False by the time the host sees the final
        # token.  (An eager .at[].set here would be one more dispatch per
        # retired request.)
        if r.slot in self._active and self._active[r.slot] is r:
            del self._active[r.slot]
            self._free_slots.append(r.slot)
            # on the chip the request ended with the step (or round) that
            # made its last token: that share of the program's run, the
            # steps of one program taking the same time each
            prog, r.retired_at = self._emitting, time.monotonic()
            ended = prog.start + (prog.done - prog.start) * (
                self._emit_step + 1) / prog.steps
            self.retired_requests += 1
            self._ended.append(
                f"{self._emit_step}:{1e3 * (prog.done - ended):.3f}")
            self._obs_retire(r, self._slots.retire(r.slot, ended,
                                                   r.retired_at))
            if self.paged and r.pages:
                # refcounted: shared prefix pages survive on the prefix
                # cache's refs; private pages return to the free list.
                # In-flight decode steps may still write into released
                # pages, but every such position is re-written by its next
                # owner's prefill/decode before it becomes readable.
                self.allocator.release(r.pages)
                r.pages = []
        r.out.put(_FLUSH)


# ---------------------------------------------------------------------------
# Serve deployment
# ---------------------------------------------------------------------------

class LLMServer:
    """Streaming LLM endpoint: body {"tokens": [...], "max_tokens": N,
    "temperature": t} -> streamed token ids (one per chunk).

    Deploy via ``llm_deployment(...)``.
    """

    def __init__(self, preset: str = "tiny", num_slots: int = 8,
                 max_len: Optional[int] = None, seed: int = 0,
                 engine_kwargs: Optional[dict] = None):
        from ray_tpu.models import config as mcfg
        cfg = (mcfg.tiny() if preset == "tiny"
               else mcfg.PRESETS[preset]())
        self.engine = LLMEngine(cfg, num_slots=num_slots, max_len=max_len,
                                seed=seed, **(engine_kwargs or {}))

    #: tokens yielded to callers and, summed over them, the seconds from a
    #: token's _emit on the engine thread to its yield on the replica's
    #: loop, in its two parts: to the return of ``req.out.get`` on the
    #: executor thread (the queue, that thread's wake-up) and from there to
    #: the loop resuming the generator (the hand-over, the loop's turn);
    #: then how long the ``yield`` held the generator (what is downstream
    #: of it: the actor's streaming reply and its back-pressure, or the
    #: replica's buffer).  Written by the replica's loop alone.  Class
    #: attributes, so that a subclass with its own constructor counts from
    #: zero too.
    delivered_tokens = 0
    deliver_lag_s = 0.0
    deliver_thread_s = 0.0
    deliver_loop_s = 0.0
    yield_hold_s = 0.0
    #: the replica's account of a request's way in and out
    #: (observability.RequestAccount), set by the replica actor that holds
    #: this deployment; None for a server nobody serves
    request_account = None

    async def __call__(self, request):
        """Async generator: polls the engine's token queue off-loop so one
        stream never blocks the replica's event loop (other streams, health
        checks and queue-length probes keep flowing)."""
        import asyncio

        body = request.json() if hasattr(request, "json") else request
        tokens = body["tokens"]
        track = obs.current_request()
        req = self.engine.submit(
            tokens, max_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 0.0)),
            eos_id=body.get("eos_id"), track=track)
        loop = asyncio.get_event_loop()
        delivered = 0

        def take():
            return req.out.get(), time.monotonic()

        while True:
            item, got = await loop.run_in_executor(None, take)
            if not isinstance(item, int):
                if isinstance(item, BaseException):
                    raise item
                if track is not None:
                    track.ended_at = req.retired_at
                return  # _FLUSH
            # each token against its own emit stamp: one "last emit" stamp
            # would under-read exactly when delivery falls a dispatch behind
            now = time.monotonic()
            thread, turn = got - req.emit_times[delivered], now - got
            self.deliver_thread_s += thread
            self.deliver_loop_s += turn
            self.deliver_lag_s += thread + turn
            self.delivered_tokens += 1
            delivered += 1
            yield item
            self.yield_hold_s += time.monotonic() - now

    def stats(self) -> dict:
        """Cumulative counters and the serving picture.  Two calls give a
        reader rates over the time between them (``t_mono``)."""
        # where this replica runs, as its own JAX reports it: the process
        # that owns the chip is the only one that can say
        devices = self.engine._jax.devices()
        return {"steps": self.engine.steps,
                "tokens_out": self.engine.tokens_out,
                "active": len(self.engine._active),
                "free_slots": len(self.engine._free_slots),
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(devices),
                "prefill_buckets": sorted(self.engine._prefill_fns),
                "delivered_tokens": self.delivered_tokens,
                "deliver_lag_s": self.deliver_lag_s,
                "deliver_thread_s": self.deliver_thread_s,
                "deliver_loop_s": self.deliver_loop_s,
                "yield_hold_s": self.yield_hold_s,
                **(self.request_account.snapshot()
                   if self.request_account is not None else {}),
                **self.engine.counters(),
                **self.engine.breakdown()}

    def prefix_digest(self) -> Optional[dict]:
        """Replica heartbeat hook (replica.py health_check attaches this
        next to the SLO snapshot): the engine's bounded first-page prefix
        digest for cache-aware routing.  Size-capped by the
        ``serve_prefix_digest_max`` knob; None (dense engine / prefix
        cache off) means the router uses pure p2c for this replica."""
        from ray_tpu.core.config import get_config
        cap = int(getattr(get_config(), "serve_prefix_digest_max", 32))
        return self.engine.prefix_digest(cap)


def llm_deployment(preset: str = "tiny", *, num_replicas: int = 1,
                   num_slots: int = 8, max_len: Optional[int] = None,
                   route_prefix: Optional[str] = None,
                   engine_kwargs: Optional[dict] = None, **options):
    """Build the Serve deployment for an LLM preset."""
    dep = serve_deployment(
        LLMServer, name=f"llm-{preset}", num_replicas=num_replicas,
        route_prefix=route_prefix, **options)
    return dep.bind(preset=preset, num_slots=num_slots, max_len=max_len,
                    engine_kwargs=engine_kwargs)
