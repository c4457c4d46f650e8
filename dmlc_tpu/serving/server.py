"""Serving HTTP endpoint: POST /generate + the telemetry surface.

The same lightweight pattern as ``telemetry.TelemetryHTTPServer`` (a
``ThreadingHTTPServer`` with daemon handler threads), extended with a
request body: each handler thread submits into the engine's bounded
admission queue and parks on the request until the continuous batcher
finishes it — so the HTTP concurrency model is "one cheap parked
thread per in-flight request" and the *engine* decides the actual
batch, which is the whole point of iteration-level scheduling.

Backpressure is explicit at the edge: when no admission slot frees
within the engine's timeout the client gets **429** with Retry-After,
not a silently growing queue.  Malformed bodies get 400; a request the
cache could never hold gets 413; an engine-side failure gets 503.

Graceful drain (preemption notice): ``drain()`` — or SIGTERM once
``install_drain_handler()`` armed it — stops admitting (new /generate
requests get **503 + Retry-After**, pointing the load balancer at
another replica), lets active decodes finish within
``DMLC_SERVE_DRAIN_S``, then closes the listener; in-flight
generations are never dropped by the shutdown notice itself.

Endpoints:
  POST /generate   {"prompt": [int, ...], "max_tokens": int?,
                    "priority": int|class-name?, "tenant": str?}
                   → request result document (scheduler.Request.result).
                   priority is a validated class (scheduler
                   PRIORITY_CLASSES or an int under
                   DMLC_SERVE_PRIORITY_LEVELS): admission and
                   KV-pressure eviction prefer low-priority victims
  GET  /metrics    local Prometheus exposition (serving + step-ledger +
                   hand-rendered dmlc_slo_* families)
  GET  /healthz    engine stats: queues, KV pool, ledger + request
                   summaries
  GET  /requests   request ledger document: summary percentiles
                   (TTFT = queue + prefill, TBT), live + recent
                   requests, decode-iteration ring (router load signal)
  GET  /slo        SLO burn-rate document (objectives, windows, active
                   violations); the GET forces a fresh evaluation
  GET  /compute    compute observability document: per-jit-site compile
                   ledger (traces/hits/recompiles, cost analysis),
                   recompile-storm verdict, HBM accounting, decode
                   phase shares, step-ledger roofline
  GET  /trace      this replica's local Chrome trace — engine threads
                   plus one labeled row per request and SLO-violation
                   instant markers (tracker-launched replicas ALSO ship
                   the same spans via heartbeats onto the merged
                   cluster /trace)
  GET  /spans      incremental span export (``?since=N&limit=M`` →
                   spans + last_seq + anchor_epoch) — what the
                   router's fleet trace assembler polls to join this
                   replica's request lifecycles into cross-process
                   journeys (DMLC_TRACE_FLEET)

Every ``/generate`` response increments a per-status-code counter
(``dmlc_serving_http_<code>``), so admission pressure (429), oversize
rejections (413), and crash-guard failures (503) are visible on
/metrics without log scraping; a POST to an unknown path counts as
``http_404`` (a misrouted client).  GET 404s are deliberately NOT
counted — monitoring tools probe optional endpoints by design, and a
watcher must never fabricate the signal it renders.
"""

from __future__ import annotations

import json
import logging
import signal
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from .. import telemetry
from ..telemetry import core as _tcore
from ..telemetry import tracecontext
from ..telemetry.exporters import to_chrome_trace
from .engine import (AdmissionFull, EngineDraining, InferenceEngine,
                     RequestTooLarge)

__all__ = ["ServingHTTPServer"]

logger = logging.getLogger("dmlc_tpu.serving")

MAX_BODY_BYTES = 1 << 20  # a prompt is ids, not a payload dump

#: the status codes /generate can answer with, each its own registered
#: counter family (a dynamic f-string name would mint unregistered
#: families); anything else folds to http_other
_STATUS_COUNTERS = {200: "http_200", 400: "http_400", 404: "http_404",
                    413: "http_413", 429: "http_429", 503: "http_503"}


def _local_trace(engine: InferenceEngine) -> dict:
    """The standalone replica's /trace document: the local span ring
    (engine threads + per-request ledger rows) with SLO violations as
    instant markers on the same span timebase."""
    doc = to_chrome_trace()
    anchor = _tcore.anchor_epoch()
    for m in engine.slo.trace_markers():
        doc["traceEvents"].append({
            "name": str(m["name"]), "cat": "slo", "ph": "i", "s": "g",
            "ts": round(max((float(m["t"]) - anchor) * 1e6, 0.0), 3),
            "pid": 0, "tid": 0,
        })
    return doc


class ServingHTTPServer:
    """HTTP front end over an :class:`InferenceEngine`."""

    def __init__(self, engine: InferenceEngine, host: str = "127.0.0.1",
                 port: int = 0, request_timeout_s: float = 300.0):
        eng = engine
        wait_s = float(request_timeout_s)

        class Handler(BaseHTTPRequestHandler):
            def _send(self, code: int, ctype: str, body: bytes,
                      extra_headers=None) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _send_json(self, code: int, doc, extra_headers=None) -> None:
                self._send(code, "application/json",
                           json.dumps(doc).encode(),
                           extra_headers=extra_headers)

            def _answer(self, code: int, doc, extra_headers=None) -> None:
                """A /generate response: counted per status code so the
                admission/failure mix is a /metrics query, then sent."""
                telemetry.inc("serving",
                              _STATUS_COUNTERS.get(code, "http_other"))
                self._send_json(code, doc, extra_headers=extra_headers)

            def do_GET(self):  # noqa: N802 - http.server API
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    text = (telemetry.to_prometheus_text()
                            + eng.slo.prometheus_text()
                            + telemetry.compute.prometheus_text()
                            + eng.availability.prometheus_text())
                    self._send(200,
                               "text/plain; version=0.0.4; charset=utf-8",
                               text.encode())
                elif path == "/healthz":
                    self._send_json(200, {"status": "ok", **eng.stats()})
                elif path == "/goodput":
                    # the serving twin of the training /goodput: this
                    # replica's availability ledger (state fractions sum
                    # to 1, tokens served vs. capacity-tokens)
                    self._send_json(200, eng.availability.report())
                elif path == "/compute":
                    self._send_json(200, telemetry.compute.report())
                elif path == "/requests":
                    self._send_json(200, eng.requests.report())
                elif path == "/slo":
                    eng.slo.evaluate()
                    self._send_json(200, eng.slo.report())
                elif path == "/trace":
                    try:
                        body = json.dumps(_local_trace(eng)).encode()
                    except (TypeError, ValueError) as e:
                        logger.warning("/trace render failed: %r", e)
                        self._send(503, "text/plain",
                                   b"trace render failed\n")
                        return
                    self._send(200, "application/json", body)
                elif path == "/spans":
                    # incremental span export for the fleet trace
                    # assembler (router pull): resume from last_seq,
                    # place on the wall clock via anchor_epoch
                    since = limit = 0
                    _, _, qs = self.path.partition("?")
                    for part in qs.split("&"):
                        k, _, v = part.partition("=")
                        try:
                            if k == "since":
                                since = int(v)
                            elif k == "limit":
                                limit = int(v)
                        except ValueError:
                            pass
                    spans, last = _tcore.spans_since(
                        since, limit=limit or 4096)
                    self._send_json(200, {
                        "spans": spans, "last_seq": last,
                        "anchor_epoch": _tcore.anchor_epoch()})
                else:
                    # GET 404s are NOT counted: monitoring tools probe
                    # optional endpoints by design (dmlc-top polls
                    # /anomalies on every target), and a watcher must
                    # never fabricate the counter it renders
                    self._send(404, "text/plain", b"not found\n")

            def do_POST(self):  # noqa: N802 - http.server API
                path = self.path.split("?", 1)[0]
                if path != "/generate":
                    # a POST to a wrong path IS a misrouted request
                    telemetry.inc("serving", "http_404")
                    self._send(404, "text/plain", b"not found\n")
                    return
                # the request's time on this handler thread, body parse
                # to response written: what it spends outside the engine
                # is serving.http_secs - serving.latency_secs
                with telemetry.span("serving.http",
                                    stage="serving") as span_args:
                    self._generate(span_args)

            def _generate(self, span_args):
                # NB the drain gate lives in eng.submit (raising
                # EngineDraining → 503 below), not here: the dedupe
                # lookup must run first so a router retry of
                # already-admitted work still resolves on a draining
                # replica instead of bouncing 503
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    if n > MAX_BODY_BYTES:
                        self._answer(413, {"error": "body too large"})
                        return
                    doc = json.loads(self.rfile.read(n) or b"{}")
                    prompt = doc["prompt"]
                    if (not isinstance(prompt, list)
                            or not all(isinstance(t, int) for t in prompt)):
                        raise ValueError("prompt must be a list of ints")
                    max_tokens = doc.get("max_tokens")
                    if max_tokens is not None:
                        max_tokens = int(max_tokens)
                    request_id = doc.get("request_id")
                    if request_id is not None \
                            and not isinstance(request_id, str):
                        raise ValueError("request_id must be a string")
                    priority = doc.get("priority")
                    tenant = doc.get("tenant")
                except (KeyError, ValueError, TypeError,
                        json.JSONDecodeError) as e:
                    self._answer(400, {"error": f"bad request: {e}"})
                    return
                trace_id = None
                if tracecontext.enabled():
                    # the fleet trace context rides X-DMLC-Trace; when
                    # the upstream sent none, derive it from the
                    # idempotency key so both ends agree anyway
                    parsed = tracecontext.parse_header(
                        self.headers.get(tracecontext.TRACE_HEADER))
                    if parsed:
                        trace_id = parsed[0]
                    elif request_id:
                        trace_id = tracecontext.mint_trace_id(request_id)
                try:
                    # request_id is the idempotency key: a duplicate of
                    # a live or recently finished request returns the
                    # SAME request (no second generation) — see
                    # InferenceEngine.submit.  priority/tenant are
                    # validated inside submit (ValueError → 400 below)
                    req = eng.submit(prompt, max_new_tokens=max_tokens,
                                     request_id=request_id,
                                     priority=priority, tenant=tenant,
                                     trace_id=trace_id)
                except AdmissionFull as e:
                    self._answer(429, {"error": str(e)},
                                 extra_headers={"Retry-After": "1"})
                    return
                except RequestTooLarge as e:
                    self._answer(413, {"error": str(e)})
                    return
                except EngineDraining as e:
                    self._answer(503, {"error": str(e)},
                                 extra_headers={"Retry-After": "5"})
                    return
                except ValueError as e:
                    # content errors (out-of-vocab ids, bad bounds) are
                    # the client's 400, not a size problem
                    self._answer(400, {"error": str(e)})
                    return
                span_args["req"] = req.id
                if not req.wait(wait_s):
                    self._answer(503, {"error": "generation timed out",
                                       "id": req.id})
                    return
                doc = req.result()
                if req.error:
                    if getattr(req, "rejected_busy", False):
                        # a duplicate that parked on an original whose
                        # admission then failed: same verdict the
                        # original got (429), not a generic 503
                        self._answer(429, doc,
                                     extra_headers={"Retry-After": "1"})
                    else:
                        self._answer(503, doc)
                else:
                    self._answer(200, doc)

            def log_message(self, fmt, *args):
                logger.debug("serving http: " + fmt, *args)

        class _Server(ThreadingHTTPServer):
            # a burst of simultaneous connects (an offline-mode load
            # test submitting its whole request set at once) overflows
            # the 5-entry default listen backlog and the kernel RSTs
            # the overflow; size it to the admission queue instead
            request_queue_size = 128

        self._httpd = _Server((host, port), Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = self._httpd.server_address[1]
        self.engine = engine
        self._drain_done = threading.Event()
        # dmlc-check: unguarded(owner-thread close() latch; double shutdown is benign)
        self._closed = False
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="serving-http")
        self._thread.start()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def drain(self, timeout_s=None) -> bool:
        """Graceful shutdown: stop admitting (new /generate → 503 +
        Retry-After), finish active decodes within ``timeout_s``
        (``DMLC_SERVE_DRAIN_S``), then close the listener.  Returns
        whether the backlog drained cleanly."""
        logger.info("serving drain: refusing new work, finishing %d "
                    "active / %d waiting", self.engine.scheduler.n_active,
                    self.engine.scheduler.n_waiting)
        clean = self.engine.drain(timeout_s)
        self.close()
        return clean

    def install_drain_handler(self) -> None:
        """Arm SIGTERM as the drain trigger (main thread only — signal
        module constraint).  A preemption notice then drains instead of
        dropping in-flight generations; ``wait_drained()`` blocks until
        the drain completes (or ``DMLC_SERVE_DRAIN_S`` cuts it off)."""
        def run_drain():
            try:
                self.drain()
            finally:
                self._drain_done.set()

        def on_term(signum, frame):  # noqa: ARG001 - signal API
            # the handler must return fast; drain on a helper thread
            threading.Thread(target=run_drain, daemon=True,
                             name="serving-drain").start()

        signal.signal(signal.SIGTERM, on_term)

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        """Block until a signal-triggered drain has fully completed."""
        return self._drain_done.wait(timeout)

    def close(self) -> None:
        if self._closed:  # drain() + the caller's finally both close
            return
        self._closed = True
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)
