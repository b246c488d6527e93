"""Serve streaming + ASGI tests (reference: http_proxy.py streaming
StreamingResponses through uvicorn; serve.ingress mounting FastAPI).

The incrementality assertion is the point: chunks must reach the client
WHILE the generator is still producing, not after it finishes.
"""
import http.client
import json
import time

import pytest


def _http(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request(method, path, body=body)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _read_chunks_timed(port, path):
    """Stream a response, recording arrival time per chunk batch."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    assert resp.status == 200
    arrivals = []
    while True:
        piece = resp.read1(65536)
        if not piece:
            break
        arrivals.append((time.monotonic(), piece))
    conn.close()
    return arrivals


@pytest.fixture
def serve_instance(ray_start_regular):
    from ray_tpu import serve

    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    yield serve
    serve.shutdown()


def test_streaming_response_chunks_arrive_incrementally(serve_instance):
    serve = serve_instance

    @serve.deployment
    def ticker(request):
        def gen():
            for i in range(5):
                yield f"tick-{i};"
                time.sleep(0.3)
        return serve.StreamingResponse(gen(), content_type="text/plain")

    serve.run(ticker.bind(), route_prefix="/tick")
    port = serve.http_port()
    t0 = time.monotonic()
    arrivals = _read_chunks_timed(port, "/tick")
    total = time.monotonic() - t0
    body = b"".join(p for _, p in arrivals)
    assert body == b"".join(f"tick-{i};".encode() for i in range(5))
    # first chunk must land while later chunks are still being produced:
    # generation takes ~1.5s; an un-streamed response would deliver
    # everything at the end
    first_at = arrivals[0][0] - t0
    assert total >= 1.2, f"generator finished too fast ({total:.2f}s)"
    assert first_at < total / 2, (
        f"first chunk at {first_at:.2f}s of {total:.2f}s — not streamed")


def test_bare_generator_streams_and_handle_iterates(serve_instance):
    serve = serve_instance

    @serve.deployment
    class Tokens:
        def __call__(self, request):
            return self.tokens()

        def tokens(self):
            for t in ["alpha", "beta", "gamma"]:
                yield t + " "

    serve.run(Tokens.bind(), route_prefix="/tok")
    port = serve.http_port()
    status, data = _http(port, "GET", "/tok")
    assert status == 200 and data == b"alpha beta gamma "

    # handle-level: the caller gets a chunk iterator
    handle = serve.get_app_handle("default")
    out = b"".join(handle.tokens.remote().result(timeout_s=30))
    assert out == b"alpha beta gamma "


def test_asgi_app_full_and_streaming(serve_instance):
    """A hand-rolled ASGI 3.0 app (no FastAPI dependency) mounted via
    serve.ingress: JSON echo + a streaming endpoint."""
    serve = serve_instance

    async def asgi_app(scope, receive, send):
        assert scope["type"] == "http"
        if scope["path"].endswith("/stream"):
            await send({"type": "http.response.start", "status": 200,
                        "headers": [(b"content-type", b"text/plain")]})
            for i in range(3):
                await send({"type": "http.response.body",
                            "body": f"s{i}.".encode(), "more_body": True})
            await send({"type": "http.response.body", "body": b"end",
                        "more_body": False})
            return
        ev = await receive()
        body = ev.get("body", b"")
        payload = json.dumps({
            "method": scope["method"],
            "path": scope["path"],
            "echo": body.decode() if body else None,
        }).encode()
        await send({"type": "http.response.start", "status": 201,
                    "headers": [(b"content-type", b"application/json"),
                                (b"x-app", b"asgi")]})
        await send({"type": "http.response.body", "body": payload,
                    "more_body": False})

    @serve.deployment
    @serve.ingress(asgi_app)
    class Api:
        pass

    serve.run(Api.bind(), route_prefix="/api")
    port = serve.http_port()

    status, data = _http(port, "POST", "/api/echo", body=b"hello")
    assert status == 201
    reply = json.loads(data)
    assert reply == {"method": "POST", "path": "/api/echo",
                     "echo": "hello"}

    status, data = _http(port, "GET", "/api/stream")
    assert status == 200 and data == b"s0.s1.s2.end"


def test_fastapi_app_if_available(serve_instance):
    fastapi = pytest.importorskip("fastapi")
    serve = serve_instance
    app = fastapi.FastAPI()

    @app.get("/hello")
    def hello():
        return {"msg": "hi"}

    @serve.deployment
    @serve.ingress(app)
    class Api:
        pass

    serve.run(Api.bind(), route_prefix="/f")
    status, data = _http(serve.http_port(), "GET", "/f/hello")
    assert status == 200 and json.loads(data) == {"msg": "hi"}


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-v", "-x"]))
