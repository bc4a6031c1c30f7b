"""The trace reduction on small synthetic traces: busy union, idle share,
exposed-collective share, per-name tables, idle attribution."""

from benchmark.lib import trace as T

MS = 1_000_000


def dev(n, ops, mods, async_ops=()):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "Async XLA Ops", "events": list(async_ops)},
        {"name": "XLA Modules", "events": mods},
        {"name": "Steps", "events": [("7", 0, 100 * MS)]}]}


def hlo(name, opcode="fusion", extra=""):
    return f"%{name} = bf16[8,128]{{1,0}} {opcode}(%p.1){extra}"


HOST = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
    ("bench:read_loss", 40 * MS, 25 * MS)]}]}


def test_interval_arithmetic():
    assert T.union([(5, 9), (0, 3), (2, 6), (20, 20)]) == [(0, 9)]
    assert T.total(T.union([(0, 3), (10, 12)])) == 5
    assert T.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]) == \
        [(0, 2), (3, 8), (22, 29)]
    assert T.base_name("fusion.123") == "fusion"
    assert T.base_name("jit_admit_fn(4099)") == "jit_admit_fn"
    assert T.base_name("all-gather-start.2") == "all-gather-start"
    kernel = hlo("closed_call.6", "custom-call",
                 ', custom_call_target="tpu_custom_call"')
    assert T.op_name(kernel) == "closed_call.6 [pallas]"
    assert T.base_name(T.op_name(kernel)) == "closed_call [pallas]"
    assert T.op_name(hlo("fusion.3")) == "fusion.3"
    assert T.op_name("jit_step(12)") == "jit_step(12)"


def test_self_time_of_nested_events():
    rows = T.self_times([("while.1", 0, 100), ("fusion.1", 0, 40),
                         ("while.2", 40, 50), ("fusion.2", 45, 20),
                         ("copy.1", 110, 5)])
    got = {r[0]: (r[3], r[4]) for r in rows}
    assert got == {"while.1": (10, False), "fusion.1": (40, True),
                   "while.2": (30, False), "fusion.2": (20, True),
                   "copy.1": (5, True)}


def test_summary_of_two_chips():
    # chip 0: an asynchronous gather runs 10..20 ms; the core computes
    # under it until 15 ms and then waits in its -done until 20 ms
    ops0 = [(hlo("while.9", "while"), 0, 25 * MS),
            (hlo("fusion.1"), 0, 10 * MS),
            (hlo("all-gather-start.1", "all-gather-start"), 10 * MS, 0),
            (hlo("fusion.2"), 10 * MS, 5 * MS),
            (hlo("all-gather-done.1", "all-gather-done"), 15 * MS, 5 * MS),
            (hlo("fusion.2"), 20 * MS, 5 * MS),
            (hlo("closed_call.3", "custom-call",
                 ', custom_call_target="tpu_custom_call"'), 70 * MS, 10 * MS)]
    async0 = [(hlo("all-gather-start.1", "all-gather-start"), 10 * MS,
               10 * MS), (hlo("copy-start.4", "copy-start"), 0, 30 * MS)]
    ops1 = [(hlo("fusion.1"), 0, 20 * MS),
            (hlo("all-reduce.5", "all-reduce"), 30 * MS, 10 * MS)]
    mods = [("jit_step(1)", 0, 40 * MS), ("jit_step(1)", 65 * MS, 20 * MS)]
    s = T.summarize([dev(0, ops0, mods, async0), dev(1, ops1, mods), HOST],
                    window_s=0.1)
    d0, d1 = s["devices"]
    assert abs(d0["busy_s"] - 0.035) < 1e-9      # [0,25] + [70,80]
    assert abs(d0["collective_s"] - 0.010) < 1e-9
    assert abs(d0["collective_exposed_s"] - 0.005) < 1e-9
    assert abs(d1["busy_s"] - 0.030) < 1e-9
    assert abs(d1["collective_exposed_s"] - 0.010) < 1e-9
    assert abs(s["busy_s"] - 0.0325) < 1e-9      # mean over the chips
    assert abs(s["collective_exposed_s"] - 0.0075) < 1e-9
    ops = {r[0]: r for r in s["ops"]}
    assert abs(ops["fusion"][1] - 0.020) < 1e-9 and ops["fusion"][2] == 2.0
    assert abs(ops["while"][1] - 0.0) < 1e-9     # all of it is its body's
    sec, count = T.seconds_matching(s["ops"], r" \[pallas\]$")
    assert abs(sec - 0.005) < 1e-9 and count == 0.5
    sec, count = T.seconds_matching(s["ops"], r"^all-(gather|reduce)")
    assert abs(sec - 0.0075) < 1e-9 and count == 1.0
    idle = {r[0]: r[1] for r in s["idle"]}
    # the 25 ms between the two programs lie under the benchmark's own span
    assert abs(idle["read_loss"] - 0.025) < 1e-9
    assert "inside programs, between operations" in idle
    assert s["window_s"] == 0.1       # the host's span covers the events'
    short = T.summarize([dev(0, ops0, mods, async0)], window_s=0.05)
    assert abs(short["window_s"] - 0.085) < 1e-9   # events span 0..85 ms
    assert short["busy_s"] <= short["window_s"]
    b = T.breakdown(s)
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][0] == "fusion"


def test_gap_without_a_span_is_named_by_its_neighbours():
    mods = [("jit__lambda_(1)", 0, 10 * MS),
            ("jit_admit_fn(2)", 30 * MS, 5 * MS)]
    s = T.summarize([dev(0, [(hlo("fusion.1"), 0, 10 * MS)], mods)], 0.05)
    assert s["idle"][0][0] == "jit__lambda_ -> jit_admit_fn"
    assert abs(s["idle"][0][1] - 0.020) < 1e-9


def test_no_device_plane_means_nothing_to_read():
    from benchmark.lib.readers import device_idle_share
    s = T.summarize([HOST], 1.0)
    assert s["devices"] == [] and s["busy_s"] == 0.0
    assert device_idle_share({"trace": s}) is None
