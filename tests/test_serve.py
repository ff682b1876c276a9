"""Serve: deployments, replicas, routing, autoscaling, HTTP.

Mirrors the reference's Serve test areas (ray: python/ray/serve/tests/
test_deploy.py, test_handle.py, test_autoscaling_policy.py,
test_proxy.py).
"""

import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    serve.start()
    yield
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


class TestDeploy:
    def test_function_deployment(self, cluster):
        @serve.deployment
        def square(x=0):
            return {"result": x * x}

        h = serve.run(square.bind(), name="sq", route_prefix=None)
        assert h.remote(x=7).result()["result"] == 49
        serve.delete("sq")

    def test_class_deployment_with_state(self, cluster):
        @serve.deployment
        class Greeter:
            def __init__(self, greeting):
                self.greeting = greeting

            def __call__(self, name="world"):
                return f"{self.greeting}, {name}!"

            def shout(self, name="world"):
                return f"{self.greeting.upper()}, {name.upper()}!"

        h = serve.run(Greeter.bind("hello"), name="greet", route_prefix=None)
        assert h.remote(name="tpu").result() == "hello, tpu!"
        assert h.options(method_name="shout").remote().result() == "HELLO, WORLD!"
        serve.delete("greet")

    def test_multiple_replicas_balance(self, cluster):
        @serve.deployment(num_replicas=2)
        class WhoAmI:
            def __call__(self):
                import os

                return os.getpid()

        h = serve.run(WhoAmI.bind(), name="who", route_prefix=None)
        pids = {h.remote().result() for _ in range(20)}
        assert len(pids) == 2
        serve.delete("who")

    def test_redeploy_updates(self, cluster):
        @serve.deployment
        def version():
            return "v1"

        h = serve.run(version.bind(), name="ver", route_prefix=None)
        assert h.remote().result() == "v1"

        @serve.deployment(name="version")
        def version2():
            return "v2"

        h2 = serve.run(version2.bind(), name="ver", route_prefix=None)
        deadline = time.time() + 30
        while time.time() < deadline:
            if h2.remote().result() == "v2":
                break
            time.sleep(0.2)
        assert h2.remote().result() == "v2"
        serve.delete("ver")

    def test_status(self, cluster):
        @serve.deployment(num_replicas=2)
        def noop():
            return 1

        serve.run(noop.bind(), name="st", route_prefix=None)
        deadline = time.time() + 30
        while time.time() < deadline:
            s = serve.status()
            if s.get("st", {}).get("noop", {}).get("running_replicas") == 2:
                break
            time.sleep(0.2)
        assert serve.status()["st"]["noop"]["running_replicas"] == 2
        serve.delete("st")

    def test_replica_error_propagates(self, cluster):
        @serve.deployment
        def broken():
            raise ValueError("replica boom")

        from ray_tpu.core.errors import TaskError

        h = serve.run(broken.bind(), name="brk", route_prefix=None)
        with pytest.raises(TaskError, match="replica boom"):
            h.remote().result()
        serve.delete("brk")


class TestAutoscaling:
    def test_scale_up_and_down(self, cluster):
        @serve.deployment(
            autoscaling_config={
                "min_replicas": 1,
                "max_replicas": 3,
                "target_ongoing_requests": 1.0,
                "upscale_delay_s": 0.5,
                "downscale_delay_s": 1.0,
            }
        )
        class Slow:
            async def __call__(self):
                import asyncio

                await asyncio.sleep(0.4)
                return 1

        h = serve.run(Slow.bind(), name="auto", route_prefix=None)
        # sustained concurrent load, until a second replica has been
        # seen: two worker starts under a loaded host take what they take
        t_end = time.time() + 30
        peak = 1
        responses = []
        while peak < 2 and time.time() < t_end:
            responses = [h.remote() for _ in range(6)]
            s = serve.status()["auto"]["Slow"]
            peak = max(peak, s["running_replicas"])
            for r in responses:
                r.result(timeout_s=30)
        assert peak >= 2, f"never scaled up (peak={peak})"
        # idle: scale back toward min
        deadline = time.time() + 30
        while time.time() < deadline:
            s = serve.status()["auto"]["Slow"]
            if s["running_replicas"] == 1:
                break
            time.sleep(0.5)
        assert serve.status()["auto"]["Slow"]["running_replicas"] == 1
        serve.delete("auto")


class TestHTTP:
    def test_http_roundtrip(self, cluster):
        @serve.deployment
        def adder(a=0, b=0):
            return {"sum": int(a) + int(b)}

        serve.run(
            adder.bind(), name="http_app", route_prefix="/add",
            http_port=18713,
        )
        import httpx

        deadline = time.time() + 30
        last = None
        while time.time() < deadline:
            try:
                r = httpx.post(
                    "http://127.0.0.1:18713/add", json={"a": 2, "b": 40},
                    timeout=10,
                )
                last = r
                if r.status_code == 200:
                    break
            except Exception:
                time.sleep(0.3)
        assert last is not None and last.status_code == 200, last
        assert last.json() == {"sum": 42}
        # query params too
        r = httpx.get("http://127.0.0.1:18713/add?a=1&b=2", timeout=10)
        assert r.json() == {"sum": 3}
        serve.delete("http_app")


class TestAsgiIngress:
    def test_two_route_asgi_app_through_proxy(self, cluster):
        """@serve.ingress (ray: serve/api.py:172): a plain ASGI app with
        its OWN path routing mounts on a deployment; both routes work
        through the HTTP proxy with the route prefix stripped, and the
        deployment class's state is reachable from the app."""
        import json as _json

        async def asgi_app(scope, receive, send):
            assert scope["type"] == "http"
            msg = await receive()
            body = msg.get("body") or b""
            path, method = scope["path"], scope["method"]
            if path == "/hello" and method == "GET":
                q = scope["query_string"].decode()
                payload = {"route": "hello", "q": q}
                status = 200
            elif path == "/echo" and method == "POST":
                payload = {"route": "echo", "got": body.decode()}
                status = 200
            else:
                payload = {"error": f"no ASGI route {method} {path}"}
                status = 404
            data = _json.dumps(payload).encode()
            await send({
                "type": "http.response.start",
                "status": status,
                "headers": [
                    (b"content-type", b"application/json"),
                    (b"x-asgi-served", b"1"),
                ],
            })
            await send({"type": "http.response.body", "body": data})

        @serve.deployment
        @serve.ingress(asgi_app)
        class WebApp:
            def __init__(self):
                self.booted = True

        serve.run(WebApp.bind(), name="asgi_app", route_prefix="/web")
        # the proxy actor is a detached singleton: ask it for the port it
        # ACTUALLY bound (an earlier test may have started it already)
        from ray_tpu.serve import api as serve_api

        proxy = serve_api._get_or_create_proxy(18714)
        port = ray_tpu.get(proxy.start.remote(), timeout=60)
        base = f"http://127.0.0.1:{port}"
        import httpx

        deadline = time.time() + 30
        r = None
        while time.time() < deadline:
            try:
                r = httpx.get(f"{base}/web/hello?who=x", timeout=10)
                if r.status_code == 200:
                    break
            except Exception:
                pass
            time.sleep(0.3)
        assert r is not None and r.status_code == 200, r
        assert r.json() == {"route": "hello", "q": "who=x"}
        assert r.headers["x-asgi-served"] == "1"
        # second route, different method, body passes through
        r = httpx.post(f"{base}/web/echo", content=b"ping", timeout=10)
        assert r.status_code == 200
        assert r.json() == {"route": "echo", "got": "ping"}
        # the ASGI app's own 404 surfaces (not the proxy's "no route")
        r = httpx.get(f"{base}/web/nope", timeout=10)
        assert r.status_code == 404
        assert "no ASGI route" in r.text
        serve.delete("asgi_app")

    def test_ingress_requires_class(self, cluster):
        async def app(scope, receive, send):
            pass

        with pytest.raises(TypeError):
            serve.ingress(app)(lambda x: x)


class TestFailover:
    def test_replica_death_failover(self, cluster):
        @serve.deployment(num_replicas=2)
        class P:
            def __call__(self):
                import os

                return os.getpid()

        h = serve.run(P.bind(), name="fo", route_prefix=None)
        # Draw until both replicas have served traffic; the pow-2 router can
        # briefly favour one replica while the other warms up under host load.
        pids = set()
        deadline = time.time() + 60
        while time.time() < deadline and len(pids) < 2:
            pids.add(h.remote().result(timeout_s=30))
        assert len(pids) == 2
        # kill one replica process out from under the router
        import os
        import signal

        os.kill(next(iter(pids)), signal.SIGKILL)
        # requests keep succeeding (retry drops the dead replica), and the
        # controller eventually restores 2 replicas
        ok = 0
        deadline = time.time() + 120
        while time.time() < deadline and ok < 10:
            try:
                h.remote().result(timeout_s=30)
                ok += 1
            except Exception:
                time.sleep(0.2)
        assert ok == 10
        deadline = time.time() + 60
        while time.time() < deadline:
            if serve.status()["fo"]["P"]["running_replicas"] == 2:
                break
            time.sleep(0.3)
        assert serve.status()["fo"]["P"]["running_replicas"] == 2
        serve.delete("fo")


class TestEmptyTensorBlock:
    def test_zero_row_tensor_block(self, cluster):
        import numpy as np

        from ray_tpu.data import block as block_mod

        b = block_mod.from_numpy({"x": np.ones((0, 2, 3), np.float32)})
        assert b.num_rows == 0
        out = block_mod.BlockAccessor(b).to_numpy()
        assert out["x"].shape == (0, 2, 3)
