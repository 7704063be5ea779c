"""Dependency-free HTTP front end + the ``run_tffm.py serve`` driver.

Line protocol over stdlib http.server (the repo ships no web
framework, and a scorer's wire format is one float per input line):

    POST /score      body: libsvm lines (one request line per score
                     owed; labels accepted and ignored, blank lines
                     score as the model bias). Response: one
                     ``%.6f``-formatted score per line — byte-identical
                     to a ``.score`` file of the same lines — with the
                     serving checkpoint step in ``X-FM-Step``.
                     Malformed lines are 400 with the parse error (a
                     bad request fails itself, never the process).
    GET  /healthz    JSON: alive/ready (liveness vs readiness — a
                     still-precompiling or mid-reload server is alive
                     but NOT ready; README "Serving fleet"),
                     served/published step, queue depth, request
                     counters, latency p50/p99, uptime.
    POST /reload     fleet-supervisor control surface: synchronously
                     hot-reload to the step in the body (empty body =
                     this server's configured pointer). 200 + JSON
                     after the swap; 503 when the reload failed (the
                     old step keeps serving).
    GET  /metrics    the obs registry (counters / gauges / histogram
                     buckets) in Prometheus text exposition format
                     (obs/prom.py) — the scrape endpoint; no JSONL
                     parsing needed to monitor a serving fleet.

Threading: http.server's ThreadingHTTPServer gives each connection a
thread; all of them funnel into the ScorerServer's admission queue,
which is the actual batching point — so N concurrent HTTP clients
become one padded device flush per admission window.
"""

from __future__ import annotations

import json
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from fast_tffm_tpu.data.parser import ParseError

# Per-request scoring budget for the HTTP path: far above any healthy
# flush (admission wait is milliseconds) but bounded, so a wedged
# dispatcher degrades to 503s instead of an unbounded pile of blocked
# connection threads. The in-process ScoreClient carries its own
# default; callers that want to wait forever can.
_SCORE_TIMEOUT_SECONDS = 60.0


class _Handler(BaseHTTPRequestHandler):
    server_version = "fmserve/1.0"
    protocol_version = "HTTP/1.1"

    def _reply(self, code: int, body: bytes, ctype: str,
               extra=None) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self) -> None:  # noqa: N802 - http.server contract
        if self.headers.get("Transfer-Encoding"):
            # No chunked-body support: with no Content-Length the body
            # can't be drained, and an undrained body desyncs the
            # HTTP/1.1 keep-alive stream — refuse AND drop the
            # connection so the next request can't be misparsed.
            self.close_connection = True
            self._reply(411, b"chunked bodies unsupported; send "
                             b"Content-Length\n", "text/plain")
            return
        # Drain the body BEFORE any routing reply: a 404'd POST that
        # leaves its body in the stream makes the keep-alive client's
        # NEXT request parse as garbage mid-body.
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length)
        if self.path == "/reload":
            # The fleet supervisor's reload token (README "Serving
            # fleet"): synchronous — the 200 only lands after the
            # swap, so the stagger protocol can re-admit this replica
            # knowing which step it serves. Body: optional step
            # number; empty = resolve this server's pointer.
            try:
                body = raw.decode("utf-8", errors="strict").strip()
                step = int(body) if body else None
            except ValueError as e:
                self._reply(400, f"{e}\n".encode("utf-8"),
                            "text/plain")
                return
            ok, now = self.server.fm_server.external_reload(step)
            payload = json.dumps({"ok": ok, "step": now}) + "\n"
            self._reply(200 if ok else 503,
                        payload.encode("utf-8"), "application/json")
            return
        if self.path != "/score":
            self._reply(404, b"unknown path; POST /score or "
                             b"/reload\n", "text/plain")
            return
        try:
            # decode inside the try: a non-UTF-8 body is the CALLER's
            # 400 (UnicodeDecodeError is a ValueError), not a dropped
            # connection + bare-stderr traceback out of http.server.
            body = raw.decode("utf-8", errors="strict")
            res = self.server.fm_server.score_lines(
                body.splitlines(), timeout=_SCORE_TIMEOUT_SECONDS)
        except (ParseError, ValueError) as e:
            self._reply(400, f"{e}\n".encode("utf-8"), "text/plain")
            return
        except RuntimeError as e:  # closed server mid-shutdown
            self._reply(503, f"{e}\n".encode("utf-8"), "text/plain")
            return
        except TimeoutError as e:
            # A wedged flush must cost this request a 503, not pin the
            # connection thread forever (ThreadingHTTPServer spawns
            # one per connection — unbounded pile-up otherwise).
            self._reply(503, f"{e}\n".encode("utf-8"), "text/plain")
            return
        out = "".join(f"{v:.6f}\n" for v in res.scores)
        self._reply(200, out.encode("utf-8"), "text/plain",
                    extra={"X-FM-Step": str(res.step)})

    def do_GET(self) -> None:  # noqa: N802 - http.server contract
        if self.path == "/metrics":
            from fast_tffm_tpu.obs.prom import PROM_CONTENT_TYPE
            body = self.server.fm_server.metrics_text()
            self._reply(200, body.encode("utf-8"), PROM_CONTENT_TYPE)
            return
        if self.path != "/healthz":
            self._reply(404, b"unknown path; GET /healthz or "
                             b"/metrics\n", "text/plain")
            return
        stats = self.server.fm_server.stats()
        self._reply(200, (json.dumps(stats) + "\n").encode("utf-8"),
                    "application/json")

    def log_message(self, fmt, *args):  # noqa: A003 - http.server API
        # Route access logs to the run logger at debug instead of bare
        # stderr writes (fmlint R002's no-print discipline).
        self.server.fm_server._logger.debug("http: " + fmt, *args)


class ScoreHTTPServer(ThreadingHTTPServer):
    daemon_threads = True

    def __init__(self, fm_server, host: str, port: int):
        self.fm_server = fm_server
        super().__init__((host, port), _Handler)


def make_http_server(fm_server, port: int,
                     host: str = "127.0.0.1") -> ScoreHTTPServer:
    """Bind the front end (port 0 = ephemeral; read the real one from
    ``.server_address``). The caller owns serve_forever/shutdown."""
    return ScoreHTTPServer(fm_server, host, port)


def run_serve(cfg, warmup: str = "sync") -> int:
    """The ``run_tffm.py serve <cfg>`` driver: load the published
    step, bind the HTTP front end, serve until SIGTERM/SIGINT, then
    drain and close. Returns a process exit code.

    ``warmup``: the single server compiles its shape ladder BEFORE it
    binds (``sync``), so a program the device cannot compile ends the
    process with the compiler's error instead of leaving a server that
    is alive and never ready. A fleet replica passes ``background``
    (serve/replica.py): its supervisor needs /healthz from the first
    second and routes around a replica that is not ready."""
    import signal
    import threading
    from fast_tffm_tpu.serve.server import ScorerServer
    from fast_tffm_tpu.utils.logging import get_logger
    logger = get_logger(log_file=cfg.log_file or None)
    stop = threading.Event()

    def _on_signal(signum, _frame):
        logger.info("serve: received signal %d; shutting down", signum)
        stop.set()

    # Handlers go in BEFORE the (restore + warmup) startup window: a
    # k8s/systemd stop landing mid-startup must still reach the drain
    # path below — run_end forensics matter most for exactly the slow
    # or wedged startup an operator kills.
    prev = {s: signal.signal(s, _on_signal)
            for s in (signal.SIGTERM, signal.SIGINT)}
    server = None
    httpd = None
    t = None
    try:
        server = ScorerServer(cfg, logger=logger, warmup=warmup)
        if not stop.is_set():
            httpd = make_http_server(server, cfg.serve_port,
                                     host=cfg.serve_host)
            t = threading.Thread(target=httpd.serve_forever,
                                 name="fm-serve-http", daemon=True)
            t.start()
            host, port = httpd.server_address[:2]
            logger.info("serving step %d on http://%s:%d (POST /score, "
                        "GET /healthz)", server.served_step, host, port)
            stop.wait()
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
        if httpd is not None:
            httpd.shutdown()
            t.join()
            httpd.server_close()
        if server is not None:
            # Always drain — including the bind-failure path, where
            # the scorer is already live: its threads must exit and
            # the metrics stream owes its run_end (never a stranded
            # 0-byte file).
            server.close()
    return 0
