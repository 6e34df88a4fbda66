"""HTTP ingress proxy actor.

Reference: python/ray/serve/_private/proxy.py (ProxyActor, HTTP :766) —
one actor running an HTTP server that resolves the route table from the
controller and forwards requests through DeploymentHandles.

Protocol: ``POST /<route>`` with a JSON (or raw) body calls the
deployment's ``__call__`` with the parsed body; the JSON-serialized result
comes back. ``GET /-/routes`` lists routes, ``GET /-/healthz`` probes.
"""
from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict

import ray_tpu


def _route(path: str) -> str:
    """Canonical route for a request path — the ONE normalization used
    for resolution, metric labels, and span names."""
    return path.split("?")[0].rstrip("/") or "/"


class RouteResolver:
    """Route-table → DeploymentHandle resolution + dispatch, shared by
    the HTTP and gRPC ingress actors (one pipeline to keep in sync)."""

    def __init__(self, controller, get_handle):
        self._controller = controller
        self._get_handle = get_handle
        self._handles: Dict[str, object] = {}

    def routes(self) -> Dict[str, str]:
        return ray_tpu.get(self._controller.routes.remote())

    def handle_for(self, route: str):
        """Raises KeyError for unknown routes."""
        route = _route(route)
        name = self.routes().get(route)
        if name is None:
            raise KeyError(route)
        handle = self._handles.get(name)
        if handle is None:
            handle = self._handles[name] = self._get_handle(name)
        return handle

    @staticmethod
    def call(handle, payload, timeout: float = 60.0):
        resp = handle.remote(payload) if payload is not None else handle.remote()
        return resp.result(timeout=timeout)

    @staticmethod
    def stream(handle, payload):
        return handle.stream(payload) if payload is not None else handle.stream()


@ray_tpu.remote
class ProxyActor:
    def __init__(self, http_port: int = 0):
        from ray_tpu.serve.api import _get_controller, get_deployment_handle
        from ray_tpu.serve.metrics import serve_metrics
        from ray_tpu.util import tracing

        tracing.maybe_enable_from_env()
        self._controller = _get_controller()
        self._resolver = RouteResolver(self._controller, get_deployment_handle)
        self._metrics = serve_metrics()
        proxy = self

        class Handler(BaseHTTPRequestHandler):
            # Chunked transfer encoding is an HTTP/1.1 construct; the
            # default HTTP/1.0 status line would make strict clients
            # (Go net/http etc.) read the raw chunk framing as the body.
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def _send(self, code, body: bytes, ctype="application/json"):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/-/healthz":
                    self._send(200, b'"ok"')
                elif self.path == "/-/routes":
                    self._send(200, json.dumps(proxy._routes()).encode())
                else:
                    self._handle(b"")

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self._handle(self.rfile.read(n))

            def _stream_mode(self):
                """"sse" | "ndjson" | None (reference: proxy.py streaming —
                SSE for EventSource/LLM clients, NDJSON otherwise)."""
                accept = self.headers.get("Accept", "")
                if "text/event-stream" in accept:
                    return "sse"
                if (
                    "application/x-ndjson" in accept
                    or self.headers.get("X-Stream") == "1"
                ):
                    return "ndjson"
                return None

            def _send_stream(self, items, mode: str):
                """Chunked streaming: one frame (and one HTTP chunk) per
                yielded item, flushed as produced (the LLM token-streaming
                path); the items of a run that arrived together leave in
                one write. NDJSON frames
                are JSON lines; SSE frames are ``data: <json>\\n\\n`` with
                errors as ``event: error`` (reference: serve's SSE
                responses consumed by EventSource clients)."""
                sse = mode == "sse"
                self.send_response(200)
                self.send_header(
                    "Content-Type",
                    "text/event-stream" if sse else "application/x-ndjson",
                )
                if sse:
                    self.send_header("Cache-Control", "no-cache")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def chunk(*frames: bytes) -> bool:
                    try:
                        self.wfile.write(b"".join(
                            f"{len(data):x}\r\n".encode() + data + b"\r\n" for data in frames))
                        self.wfile.flush()
                        return True
                    except OSError:
                        return False  # client went away — just stop

                def frame(item=None, error=None) -> bytes:
                    if sse:
                        if error is not None:
                            return (
                                b"event: error\ndata: "
                                + json.dumps({"error": error}).encode()
                                + b"\n\n"
                            )
                        return b"data: " + json.dumps(item, default=str).encode() + b"\n\n"
                    if error is not None:
                        return json.dumps({"error": error}).encode() + b"\n"
                    return json.dumps(item, default=str).encode() + b"\n"

                alive = True
                in_hand = getattr(items, "in_hand", list)  # what came with the item
                try:
                    for item in items:
                        alive = chunk(frame(item=item), *(frame(item=x) for x in in_hand()))
                        if not alive:
                            break
                except Exception as e:  # noqa: BLE001 — replica error → error frame
                    alive = alive and chunk(frame(error=str(e)))
                finally:
                    close = getattr(items, "close", None)
                    if close:
                        close()  # release the router's in-flight slot
                if alive:
                    try:
                        self.wfile.write(b"0\r\n\r\n")
                    except OSError:
                        pass
                else:
                    self.close_connection = True

            def _handle(self, body: bytes):
                route = _route(self.path)
                t0 = time.time()
                code = 200
                try:
                    # model-multiplexed routing (reference: the
                    # serve_multiplexed_model_id request header)
                    from ray_tpu.serve.multiplex import MODEL_ID_HEADER

                    mux_id = self.headers.get(MODEL_ID_HEADER, "")
                    mode = self._stream_mode()
                    if mode:
                        self._send_stream(
                            proxy._dispatch_stream(self.path, body, mux_id), mode
                        )
                        return
                    result = proxy._dispatch(self.path, body, mux_id)
                    self._send(200, json.dumps(result, default=str).encode())
                except KeyError:
                    code = 404
                    # Unmatched paths share ONE label value: the raw path
                    # is client-controlled, and per-path series from a
                    # scanner would grow the registry without bound.
                    route = "_unmatched"
                    self._send(404, b'{"error": "no such route"}')
                except (BrokenPipeError, ConnectionResetError):
                    # Client went away mid-response — not a server error;
                    # label with nginx's 499 so aborts don't masquerade
                    # as 500-rate on the dashboard. No response attempt:
                    # the socket is dead.
                    code = 499
                    self.close_connection = True
                except Exception as e:  # noqa: BLE001 — user errors → 500
                    code = 500
                    self._send(500, json.dumps({"error": str(e)}).encode())
                finally:
                    # Streaming responses are timed through here too: the
                    # try block returns only after the stream drained.
                    proxy._metrics.proxy_requests.inc(
                        1, {"route": route, "code": str(code)}
                    )
                    proxy._metrics.proxy_ms.observe(
                        (time.time() - t0) * 1000.0, {"route": route}
                    )

        self._server = ThreadingHTTPServer(("127.0.0.1", http_port), Handler)
        self._port = self._server.server_address[1]
        threading.Thread(target=self._server.serve_forever, daemon=True).start()

    def _routes(self) -> Dict[str, str]:
        return self._resolver.routes()

    def _resolve(self, path: str, body: bytes):
        handle = self._resolver.handle_for(path)
        try:
            payload = json.loads(body) if body else None
        except json.JSONDecodeError:
            payload = body.decode(errors="replace")
        return handle, payload

    def _dispatch(self, path: str, body: bytes, mux_id: str = ""):
        from ray_tpu.util import tracing

        with tracing.start_span(f"proxy:{_route(path)}"):
            handle, payload = self._resolve(path, body)
            if mux_id:
                handle = handle.options(multiplexed_model_id=mux_id)
            return RouteResolver.call(handle, payload)

    def _dispatch_stream(self, path: str, body: bytes, mux_id: str = ""):
        from ray_tpu.util import tracing

        # The span covers resolution + submission; the stream itself is
        # timed by _handle (proxy_ms) and the replica-side span.
        with tracing.start_span(f"proxy:{_route(path)}", {"stream": True}):
            handle, payload = self._resolve(path, body)
            if mux_id:
                handle = handle.options(multiplexed_model_id=mux_id)
            return RouteResolver.stream(handle, payload)

    def port(self) -> int:
        return self._port
