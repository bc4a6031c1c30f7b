"""One caller of a serving cell, in a process of its own: a driver attached
to the cluster (``ray_tpu.init(address=...)``) that sends one request at a
time and stamps every token as it arrives.

The traffic mix's ``ingress`` says which of the program's two streaming
calls the request takes (PERF.md, PR 24, has the measurements):

* ``"native_generator"`` (the default): the router picks a replica and the
  caller invokes ``handle_request_gen`` with ``num_returns="streaming"``,
  the call ``serve/http_proxy.py`` and ``serve/grpc_proxy.py`` make for
  every streaming request.  Each token is pushed to the caller as the
  replica yields it.
* ``"handle_stream"``: ``DeploymentHandle.stream``, the buffered
  ``handle_request_streaming`` / ``next_chunks`` polling protocol.  Today
  its polls wait behind the request itself in ``core/core_worker.py``'s
  per-actor call pump, so every token arrives when the request ends.

Why a process per caller: independent users are independent processes, and
on the polling protocol one process cannot even hold two streams (the pump
sends one batch of calls to an actor and awaits all of it).

Protocol: one JSON object per line.  In: ``{"i", "p", "o", "x", "t",
"epoch"}`` (index, prompt and output length, prefix index, start relative
to the epoch or null for "now").  Out: ``{"i", "t_start", "t_fired",
"times", "t_end", "err"}``, times relative to the epoch on the host's
monotonic clock, which all processes of a machine share.  ``{"warm": true}``
sends one 8-token request for 2 tokens and answers ``{"warmed", "err"}``: a
process's first request fires 25 ms late and its first call to the replica
takes tens of ms more now and then, which a user of a long-lived ingress
never sees, and the first request of a pre-roll sets the phase of every
dispatch after it (PERF.md, PR 28).

The caller runs with Python's cyclic collector off (``gc.disable()``).  The
program's ``ReferenceCounter`` guards its counts with a plain lock that
``ObjectRef.__del__`` takes too; a collection that starts on the IO thread
while ``add_local_ref`` holds the lock (every token makes a ref) finalizes
a ref there, and the thread waits for itself: the stream never ends and
the process is lost (PERF.md section 7, PR 34: one request in 300 at 6
requests/s).  With the collector off no finalizer runs inside the lock; a
caller lives for one run and makes no cycles to speak of.  To go when the
program's lock is re-entrant.
"""

from __future__ import annotations

import gc
import json
import sys
import time


def handle_stream(deployment: str):
    """``stream(payload, timeout_s)`` over ``DeploymentHandle.stream``."""
    from ray_tpu import serve
    handle = serve.get_deployment_handle(deployment)
    return lambda payload, timeout_s: handle.stream(payload,
                                                    timeout_s=timeout_s)


def native_generator(deployment: str):
    """``stream(payload, timeout_s)`` over the replica's native
    streaming-generator method, as the HTTP and gRPC ingresses call it."""
    import ray_tpu
    from ray_tpu.serve.router import get_router
    router = get_router()

    def stream(payload, timeout_s):
        deadline = time.monotonic() + timeout_s
        name = router.choose_replica(deployment,
                                     hint_tokens=payload["tokens"])
        gen = router._replica_handle(name).handle_request_gen.options(
            num_returns="streaming", generator_backpressure=256).remote(
                (payload,), {}, None)
        for ref in gen:
            yield ray_tpu.get(ref, timeout=max(
                0.0, deadline - time.monotonic()))
    return stream


INGRESS = {"native_generator": native_generator,
           "handle_stream": handle_stream}


def main(argv) -> int:
    address, deployment, traffic_json, vocab, seed, timeout_s = argv
    gc.disable()      # the module's text says why
    import ray_tpu

    from benchmark.lib import loadgen

    traffic = json.loads(traffic_json)
    payloads = loadgen.Payloads(traffic, int(vocab), int(seed))
    ray_tpu.init(address=address, log_to_driver=False)
    try:
        stream = INGRESS[traffic.get("ingress", "native_generator")](
            deployment)
        print(json.dumps({"ready": True}), flush=True)
        for line in sys.stdin:
            cmd = json.loads(line)
            if cmd.get("warm"):
                err = ""
                try:
                    for _tok in stream(payloads.make(loadgen.Planned(
                            None, 8, 2, -1, 0)), float(timeout_s)):
                        pass
                except Exception as e:  # noqa: BLE001 — the parent raises it
                    err = repr(e)
                print(json.dumps({"warmed": not err, "err": err}), flush=True)
                continue
            epoch = cmd["epoch"]
            req = loadgen.Planned(cmd["t"], cmd["p"], cmd["o"], cmd["x"],
                                  cmd["i"])
            payload = payloads.make(req)
            t_fired = time.monotonic() - epoch
            t_start = t_fired if cmd["t"] is None else cmd["t"]
            times, err = [], ""
            try:
                for _tok in stream(payload, float(timeout_s)):
                    times.append(time.monotonic() - epoch)
            except Exception as e:  # noqa: BLE001 — a failure is a sample
                err = repr(e)
            print(json.dumps({"i": cmd["i"], "t_start": t_start,
                              "t_fired": t_fired, "times": times,
                              "t_end": time.monotonic() - epoch,
                              "err": err}), flush=True)
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
