"""What the per-layer readers share that joins the trace with the callers'
samples: the requests in flight during the traced span, whichever way their
tokens travel."""

from benchmark.lib import readers
from benchmark.lib.loadgen import Sample


def request(t_fired, ttft, n, gap, prompt):
    """A request whose tokens stream: first after ``ttft``, then every
    ``gap``."""
    times = [t_fired + ttft + i * gap for i in range(n)]
    return Sample(t_fired, t_fired, times, n, prompt, times[-1])


def at_its_end(s):
    """The same request as the polling ingress delivers it today."""
    return Sample(s.t_start, s.t_fired, [s.t_end] * len(s.token_times),
                  s.expected_tokens, s.prompt_len, s.t_end)


def ctx(samples, prefill_s=0.5, batches=2):
    stats0 = {"admit_batches": 10, "steps": 100, "tokens_out": 1000}
    stats1 = {"admit_batches": 10 + batches, "steps": 160,
              "tokens_out": 1480}
    return {"samples": samples, "peaks": None, "config": {},
            "trace": {"programs": [["jit_admit_fn", prefill_s, batches],
                                   ["jit__lambda", 2.0, 7]], "busy_s": 2.5},
            "span": {"t0": 20.0, "t1": 26.0, "stats0": stats0,
                     "stats1": stats1}}


SAMPLES = [request(5.0, 1.0, 200, 0.1, 900),     # prefilled long before
           request(19.5, 1.0, 100, 0.1, 300),    # first token at 20.5: in
           request(24.0, 1.5, 50, 0.1, 700),     # first token at 25.5: in
           request(25.5, 1.0, 50, 0.1, 500)]     # first token at 26.5: after


def test_in_flight_is_send_to_end_whichever_way_tokens_travel():
    want = [300, 700, 500, 900]
    for samples in (SAMPLES, [at_its_end(s) for s in SAMPLES]):
        live = readers.in_flight(ctx(samples))
        assert sorted(s.prompt_len for s in live) == sorted(want)
    gone = request(1.0, 1.0, 20, 0.1, 64)          # ended at 3.9
    assert readers.in_flight(ctx([gone])) == []
