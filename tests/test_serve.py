"""Serve-equivalent tests: deploy/call, batching, streaming, rolling update,
replica death, autoscaling, HTTP proxy (reference: python/ray/serve/tests).

Mirrors the reference's test strategy (``python/ray/serve/tests/``): each test
drives the public API against a real single-node runtime.
"""

import asyncio
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def serve_runtime():
    from ray_tpu.utils.testing import CPU_WORKER_ENV
    info = ray_tpu.init(num_cpus=8, worker_env=dict(CPU_WORKER_ENV))
    yield info
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def serve_clean(serve_runtime):
    yield
    serve.shutdown()


def test_function_deployment(serve_clean):
    @serve.deployment
    def doubler(x: int) -> int:
        return 2 * x

    h = serve.run(doubler)
    assert h.remote(21).result(timeout_s=30) == 42
    st = serve.status()["doubler"]
    assert st["status"] == "HEALTHY"
    assert len(st["replicas"]) == 1


def test_class_deployment_methods_and_reconfigure(serve_clean):
    @serve.deployment(num_replicas=2, user_config={"prefix": "a"})
    class Greeter:
        def __init__(self):
            self.prefix = "?"
            self.n = 0

        def reconfigure(self, cfg):
            self.prefix = cfg["prefix"]

        def __call__(self, name: str) -> str:
            return f"{self.prefix}:{name}"

        def count(self) -> int:
            self.n += 1
            return self.n

    h = serve.run(Greeter)
    assert h.remote("bob").result(timeout_s=30) == "a:bob"
    # named-method routing
    assert h.count.remote().result(timeout_s=30) >= 1
    st = serve.status()["Greeter"]
    assert len(st["replicas"]) == 2


def test_batching(serve_clean):
    @serve.deployment
    class Batcher:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        async def __call__(self, xs):
            self.batch_sizes.append(len(xs))
            return [x * 10 for x in xs]

        def seen(self):
            return self.batch_sizes

    h = serve.run(Batcher)
    responses = [h.remote(i) for i in range(8)]
    assert [r.result(timeout_s=30) for r in responses] == [
        i * 10 for i in range(8)]
    sizes = h.seen.remote().result(timeout_s=30)
    assert max(sizes) > 1, f"no dynamic batching happened: {sizes}"


def test_streaming_handle(serve_clean):
    @serve.deployment
    def ticker(n: int):
        for i in range(n):
            yield f"tick-{i}"

    h = serve.run(ticker)
    chunks = list(h.stream(5))
    assert chunks == [f"tick-{i}" for i in range(5)]


def test_replica_death_recovery(serve_clean):
    @serve.deployment(num_replicas=2, health_check_period_s=0.2)
    def echo(x):
        return x

    h = serve.run(echo)
    st = serve.status()["echo"]
    victim = st["replicas"][0]["name"]
    ray_tpu.kill(ray_tpu.get_actor(victim))
    # Router must survive the dead replica (evict + retry) and the
    # controller must replace it.
    for i in range(20):
        assert h.remote(i).result(timeout_s=30) == i
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = serve.status()["echo"]
        names = {r["name"] for r in st["replicas"]}
        if len([r for r in st["replicas"]
                if r["state"] == "RUNNING"]) == 2 and victim not in names:
            break
        time.sleep(0.2)
    else:
        pytest.fail(f"replacement replica never became RUNNING: {st}")


def test_wait_healthy_decides_on_a_status_read_at_the_deadline(monkeypatch):
    """A controller that answers slowly (a loaded machine) and turns HEALTHY
    just past the deadline: ``serve.run`` must not raise a timeout whose own
    message says HEALTHY.  One that never does still times out."""
    from ray_tpu.serve import api

    def controller(healthy_at):
        t0, asked = time.monotonic(), []

        class SlowController:
            class get_status:                              # noqa: N801
                @staticmethod
                def remote():
                    asked.append(time.monotonic() - t0)
                    return "HEALTHY" if asked[-1] >= healthy_at \
                        else "DEPLOYING"
        return SlowController, asked

    def slow_get(status, timeout=None):
        time.sleep(0.3)
        return {"app": {"status": status}}

    monkeypatch.setattr(api.ray_tpu, "get", slow_get)
    ctrl, asked = controller(healthy_at=0.55)
    api._wait_healthy(ctrl, ["app"], timeout_s=0.5)
    assert len(asked) == 3 and asked[-1] >= 0.5
    ctrl, asked = controller(healthy_at=float("inf"))
    with pytest.raises(TimeoutError, match="DEPLOYING"):
        api._wait_healthy(ctrl, ["app"], timeout_s=0.5)
    assert len(asked) == 3


def test_rolling_update(serve_clean):
    @serve.deployment(num_replicas=2)
    def versioned(_x=None):
        return "v1"

    h = serve.run(versioned)
    assert h.remote().result(timeout_s=30) == "v1"
    old = {r["name"] for r in serve.status()["versioned"]["replicas"]}

    @serve.deployment(name="versioned", num_replicas=2)
    def versioned2(_x=None):
        return "v2"

    stop = threading.Event()
    failures = []

    def hammer():
        while not stop.is_set():
            try:
                serve.get_deployment_handle("versioned").remote().result(
                    timeout_s=30)
            except Exception as e:  # noqa: BLE001
                failures.append(e)
            time.sleep(0.05)

    t = threading.Thread(target=hammer)
    t.start()
    try:
        serve.run(versioned2, timeout_s=60)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if h.remote().result(timeout_s=30) == "v2":
                break
            time.sleep(0.2)
    finally:
        stop.set()
        t.join()
    assert h.remote().result(timeout_s=30) == "v2"
    new = {r["name"] for r in serve.status()["versioned"]["replicas"]}
    assert new.isdisjoint(old), "rolling update must replace every replica"
    assert not failures, f"requests failed during rolling update: {failures[:3]}"


def test_autoscaling_up_and_down(serve_clean):
    @serve.deployment(
        max_concurrent_queries=16,
        health_check_period_s=0.1,
        autoscaling_config=dict(min_replicas=1, max_replicas=3,
                                target_ongoing_requests=1.0,
                                upscale_delay_s=0.2, downscale_delay_s=0.5))
    class Slow:
        async def __call__(self, _x=None):
            await asyncio.sleep(0.4)
            return "ok"

    h = serve.run(Slow)
    assert len(serve.status()["Slow"]["replicas"]) == 1
    # sustained concurrent load -> scale up
    stop = threading.Event()

    def load():
        while not stop.is_set():
            responses = [h.remote() for _ in range(8)]
            for r in responses:
                try:
                    r.result(timeout_s=30)
                except Exception:
                    pass

    threads = [threading.Thread(target=load) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 45
        peak = 1
        while time.monotonic() < deadline:
            peak = max(peak, len([r for r in serve.status()["Slow"]["replicas"]
                                  if r["state"] == "RUNNING"]))
            if peak >= 2:
                break
            time.sleep(0.2)
        assert peak >= 2, "never scaled up under load"
    finally:
        stop.set()
        for t in threads:
            t.join()
    # idle -> scale back down to min
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        n = len(serve.status()["Slow"]["replicas"])
        if n == 1:
            break
        time.sleep(0.3)
    assert len(serve.status()["Slow"]["replicas"]) == 1, "never scaled down"


def test_http_proxy(serve_clean):
    import requests

    @serve.deployment(route_prefix="/math")
    class Math:
        def __call__(self, request: serve.Request):
            data = request.json()
            return {"sum": sum(data["xs"])}

    serve.run(Math, http=True)
    cfg = serve.http_config()
    base = f"http://{cfg['host']}:{cfg['port']}"
    r = requests.post(f"{base}/math", json={"xs": [1, 2, 3]}, timeout=30)
    assert r.status_code == 200
    assert r.json() == {"sum": 6}
    assert requests.get(f"{base}/nope", timeout=30).status_code == 404
    assert requests.get(f"{base}/-/healthz", timeout=30).text == "ok"


def test_http_streaming(serve_clean):
    import requests

    @serve.deployment(route_prefix="/stream")
    def streamer(request: serve.Request):
        n = int(request.query.get("n", 3))
        for i in range(n):
            yield f"c{i}\n"

    serve.run(streamer, http=True)
    cfg = serve.http_config()
    r = requests.get(f"http://{cfg['host']}:{cfg['port']}/stream?n=4",
                     timeout=30, stream=True)
    body = b"".join(r.iter_content(None)).decode()
    assert body == "c0\nc1\nc2\nc3\n"


def test_delete_deployment(serve_clean):
    @serve.deployment
    def gone(_x=None):
        return 1

    serve.run(gone)
    serve.delete("gone")
    assert "gone" not in serve.status()


def test_multiplexed_model_loading(serve_clean):
    @serve.deployment
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models=2)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return f"model:{model_id}"

        async def __call__(self, model_id: str):
            model = await self.get_model(model_id)
            return model

        def load_log(self):
            return self.loads

    h = serve.run(MultiModel)
    assert h.remote("a").result(timeout_s=30) == "model:a"
    assert h.remote("b").result(timeout_s=30) == "model:b"
    assert h.remote("a").result(timeout_s=30) == "model:a"  # cached
    assert h.load_log.remote().result(timeout_s=30) == ["a", "b"]
    # third model evicts the LRU ("b" was used less recently than "a")
    assert h.remote("c").result(timeout_s=30) == "model:c"
    assert h.remote("b").result(timeout_s=30) == "model:b"  # re-load
    assert h.load_log.remote().result(timeout_s=30) == ["a", "b", "c", "b"]


def test_a_streams_poll_may_wait_for_what_is_left_of_the_streams_budget(
        monkeypatch):
    """PR 60: a poll sent behind a stream's start rides the caller's pump
    until the request is over, so under a bound of a minute a poll an answer
    of more than a minute failed at its second poll, however sound the
    replica.  A stream with a budget gives each poll what is left of it; one
    without keeps the minute."""
    from ray_tpu.serve import router as r

    class Replica:
        class next_chunks:
            remote = staticmethod(lambda stream_id, cursor: ("poll", cursor))

        class cancel_stream:
            remote = staticmethod(lambda stream_id: None)

    class Router:
        def start_stream(self, *a):
            return "replica", "stream-1", "ref"

        def _replica_handle(self, name):
            return Replica

    waited = []

    def get(ref, timeout=None):
        waited.append(timeout)
        return (["a", "b"], 2, True) if isinstance(ref, tuple) else None

    monkeypatch.setattr(r, "get_router", lambda: Router())
    monkeypatch.setattr(r.ray_tpu, "get", get)
    assert list(r.DeploymentHandle("d").stream(1, timeout_s=300)) == ["a", "b"]
    assert 299.0 < waited[0] <= 301.0
    waited.clear()
    assert list(r.DeploymentHandle("d").stream(1)) == ["a", "b"]
    assert waited[0] == 60.0
