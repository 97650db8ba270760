"""The HTTP/1.1 transport in front of the handler cores.

Parity: the spray-can ``Http.Bind`` layer of ``data/api/EventServer.scala``
and ``core/workflow/CreateServer.scala``. A threading server, one thread a
connection, is all the transport the framework needs — handler logic lives
in the transport-agnostic service objects, matching the reference's
actor/route split and keeping tests in-process.

Of the standard library it keeps the sockets (``ThreadingHTTPServer``, the
per-connection ``setup`` / ``handle`` / ``finish``); the head of a request
is read and the head of its answer written here, in a few ``bytes``
operations a request, because every request pays for them on its thread
under the interpreter lock (PERF.md, the HTTP layer). What the transport
accepts and refuses is in docs/serving.md ("The transport").
"""

from __future__ import annotations

import json
import logging
import os
import re
import ssl
import threading
import time
import urllib.parse
from email.utils import formatdate
from http import HTTPStatus
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import TYPE_CHECKING, Callable, Mapping

from predictionio_tpu.utils import spans

__all__ = [
    "serve",
    "start_background",
    "make_ssl_context",
    "ssl_context_from_env",
]

logger = logging.getLogger(__name__)


def make_ssl_context(
    cert_path: str, key_path: str, key_password: str | None = None
) -> ssl.SSLContext:
    """Server-side TLS context from a PEM cert/key pair.

    Parity: ``common/.../configuration/SSLConfiguration.scala`` — the
    reference reads a JKS keystore via typesafe-config and hands an
    ``SSLContext`` to both spray servers; here the PEM pair comes from
    CLI flags or ``PIO_SSL_CERT``/``PIO_SSL_KEY`` env vars and wraps the
    listening socket of any framework server."""
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(cert_path, key_path, password=key_password)
    return ctx


def ssl_context_from_env() -> ssl.SSLContext | None:
    """TLS context from ``PIO_SSL_CERT``/``PIO_SSL_KEY`` (+ optional
    ``PIO_SSL_KEY_PASSWORD``), or None when unset — the deployment-env
    layer of the config triad (SURVEY.md section 6.6)."""
    cert = os.environ.get("PIO_SSL_CERT")
    key = os.environ.get("PIO_SSL_KEY")
    if not cert and not key:
        return None
    if bool(cert) != bool(key):
        # refuse to silently serve plaintext when the operator set half
        # the pair — same contract as the --cert/--key flags
        raise ValueError(
            "PIO_SSL_CERT and PIO_SSL_KEY must be set together"
        )
    return make_ssl_context(cert, key, os.environ.get("PIO_SSL_KEY_PASSWORD"))

#: signature shared with EventService.dispatch / QueryService.dispatch
Dispatcher = Callable[..., "object"]

#: requests that rode in a batch between two reads of an HTTP thread's CPU
#: clock: the read is a system call made under the interpreter lock, 0.3 us
#: on a plain Linux host and 6 us idle, more under load, on the chip's
#: (PERF.md, PR 37). A thread serves its connection's requests one after
#: another and burns next to no CPU between them, so one read a group still
#: counts every request's CPU time; the group's sums go to the service
#: together
RIDERS_A_CPU_READ = 64

#: the longest request line or header line read, and the most lines a head
#: may hold after the request line, the blank one included: the limits
#: ``http.server`` and ``http.client`` enforced before this module read
#: heads itself
_MAX_LINE = 65536
_MAX_HEADER_LINES = 100

_METHODS = frozenset({"GET", "POST", "DELETE", "PUT"})
_VERSION = re.compile(rb"HTTP/(\d{1,10})\.(\d{1,10})")

#: every answer's first line, with the standard library's phrases
_STATUS_LINES = {
    status.value: f"HTTP/1.1 {status.value} {status.phrase}\r\n".encode("latin-1")
    for status in HTTPStatus
}

_SERVER_LINE = (
    f"Server: {BaseHTTPRequestHandler.server_version} "
    f"{BaseHTTPRequestHandler.sys_version}\r\nDate: "
)

#: (second, the ``Server`` and ``Date`` lines made in it), shared by every
#: thread of every server in the process. The tuple is built, then
#: published in one store: a thread that reads the old one meanwhile
#: writes a date a second old, and two that build at once build the same
_dated: tuple[int, bytes] = (0, b"")


def _server_and_date() -> bytes:
    """An answer's ``Server`` and ``Date`` lines; the date is formatted at
    most once a second."""
    global _dated
    second = int(time.time())
    dated = _dated
    if dated[0] != second:
        line = _SERVER_LINE + formatdate(second, usegmt=True) + "\r\n"
        dated = _dated = (second, line.encode("latin-1"))
    return dated[1]


class _Refused(Exception):
    """A head this server does not serve: ``(status, message)``. The
    answer closes the connection — what follows a head that could not be
    read is not known to be a request."""


def _keeps_alive(version: bytes) -> bool:
    """Whether a request of this HTTP version keeps its connection open
    by default, by ``http.server``'s reading of the version token
    (``HTTP/<digits>.<digits>``, leading zeros ignored): 1.1 and later
    minors do, 1.0 does not; 2.0 and later is a 505, anything else,
    HTTP/0.9 included (an answer with no head cannot say how it ends),
    a 400."""
    if version == b"HTTP/1.1":
        return True
    if version == b"HTTP/1.0":
        return False
    match = _VERSION.fullmatch(version)
    number = (int(match[1]), int(match[2])) if match else (0, 0)
    if number < (1, 0):
        raise _Refused(
            400, f"Bad request version ({version[:32].decode('latin-1')!r})."
        )
    if number >= (2, 0):
        raise _Refused(505, "Invalid HTTP version.")
    return number >= (1, 1)


class _LengthReader:
    """Bounded raw-body reader (``Content-Length`` requests) handed to
    streaming routes — ``read(n)`` returns at most ``n`` bytes, ``b""``
    at end of body."""

    def __init__(self, rfile, length: int):
        self._r = rfile
        self._left = max(0, length)

    def read(self, n: int = 65536) -> bytes:
        if self._left <= 0:
            return b""
        data = self._r.read(min(n, self._left))
        if not data:
            self._left = 0
            return b""
        self._left -= len(data)
        return data

    @property
    def exhausted(self) -> bool:
        return self._left <= 0


class _ChunkedReader:
    """Incremental ``Transfer-Encoding: chunked`` request-body decoder
    (http.server does not decode chunked uploads itself). Same
    ``read(n)``/``exhausted`` contract as :class:`_LengthReader`;
    malformed framing raises ``ValueError`` (the consuming route turns
    it into a clean stream-level error)."""

    def __init__(self, rfile):
        self._r = rfile
        self._left = 0
        self._done = False
        self._broken = False

    def _torn(self, what: str) -> ValueError:
        """Malformed or truncated framing: unknown bytes may remain on
        the wire — the connection must NOT be reused (exhausted stays
        False so the handler hangs up) and the consuming route must see
        an ERROR, never a clean end-of-body (a truncated upload acked
        ok would silently lose the un-sent half)."""
        self._done = True
        self._broken = True
        return ValueError(what)

    def read(self, n: int = 65536) -> bytes:
        if self._done:
            return b""
        if self._left == 0:
            line = self._r.readline(1024)
            if not line:
                raise self._torn(
                    "connection closed before the terminating chunk"
                )
            try:
                size = int(line.split(b";")[0].strip() or b"0", 16)
            except ValueError:
                raise self._torn(f"bad chunk size line {line[:32]!r}")
            if size == 0:
                while True:  # consume optional trailers up to blank line
                    t = self._r.readline(1024)
                    if not t or t in (b"\r\n", b"\n"):
                        break
                self._done = True
                return b""
            self._left = size
        data = self._r.read(min(n, self._left))
        if not data:
            raise self._torn("connection closed mid-chunk")
        self._left -= len(data)
        if self._left == 0:
            self._r.read(2)  # CRLF closing the chunk
        return data

    @property
    def exhausted(self) -> bool:
        return self._done and not self._broken

#: readiness hook: () -> {"ready": bool, "checks": {...}} — served at
#: GET /readyz (see _make_handler)
ReadinessHook = Callable[[], Mapping]

if TYPE_CHECKING:
    from predictionio_tpu.api.lifecycle import DrainManager


def _resolve_readiness(
    dispatch: Dispatcher, readiness: ReadinessHook | None
) -> ReadinessHook | None:
    """An explicit hook wins; otherwise a service object's ``readiness``
    method is discovered from a bound ``dispatch`` — so every framework
    server (event/query/admin/dashboard/storage) gets ``/readyz`` for
    free the moment its service class defines one."""
    if readiness is not None:
        return readiness
    owner = getattr(dispatch, "__self__", None)
    hook = getattr(owner, "readiness", None)
    return hook if callable(hook) else None


def _resolve_span_sink(dispatch: Dispatcher) -> Callable | None:
    """A service object's ``record_http`` method, discovered like
    ``readiness``: it is handed the spans each request closed on its
    HTTP thread (``httpRead``, ``httpWrite``) and, once a group of
    ``RIDERS_A_CPU_READ`` requests that rode in a batch is full (or the
    connection ends), what was counted on the thread's collector over
    the group: ``rider.requestNs`` (each rider's stretch on this thread,
    the request line read to the flush, summed), ``rider.cpuNs`` (the
    thread's CPU time since the group before) and what the service
    counted (the batcher: ``rider.requests``, ``rider.queuedNs``,
    ``rider.giveWayNs``). Servers without one bind no collector and
    record nothing."""
    hook = getattr(getattr(dispatch, "__self__", None), "record_http", None)
    return hook if callable(hook) else None


def _make_handler(
    dispatch: Dispatcher,
    readiness: ReadinessHook | None = None,
    lifecycle: "DrainManager | None" = None,
):
    span_sink = _resolve_span_sink(dispatch)

    # resolved once: a service names its streaming routes on its class
    stream_routes = getattr(
        getattr(dispatch, "__self__", None), "stream_routes", None
    )

    class Handler(BaseHTTPRequestHandler):
        #: per-connection socket timeout — bounds stalled clients (incl.
        #: the lazy TLS handshake, which runs on first I/O in this
        #: worker thread; see _make_server)
        timeout = 60
        #: keep-alive clients otherwise stall ~40 ms per request on the
        #: Nagle/delayed-ACK interaction should an answer ever leave as
        #: two segments, the second waiting on the client's delayed ACK
        disable_nagle_algorithm = True
        #: buffer the response so head and body leave in one send
        #: (handle_one_request flushes wfile after each request)
        wbufsize = 64 * 1024

        def setup(self):
            super().setup()
            # one handler per connection, on a thread of its own: the
            # service's spans of this thread (never annotated into the
            # profiler: utils/spans.py) collect here, taken per request
            self._collector = spans.Collector() if span_sink else None
            spans.bind(self._collector)
            self._request_ns = 0
            self._cpu_ns = time.thread_time_ns() if span_sink else 0

        def handle_one_request(self):
            """One request of the connection: its head read here (never by
            ``http.server.parse_request`` and its ``email`` parser), then
            ``_respond``."""
            try:
                # a keep-alive thread waits for its caller in this read
                line = self.rfile.readline(_MAX_LINE + 1)
                if not line:
                    self.close_connection = True
                    return
                # the handler's first instruction on a request: the request
                # line is in hand, the header lines are parsed next
                if self._collector is not None:
                    self._request_ns = time.perf_counter_ns()
                try:
                    self._read_head(line)
                except _Refused as refused:
                    self._refuse(*refused.args)
                    return
                self._respond()
                self.wfile.flush()  # send the response if not already done
            except TimeoutError as e:
                # a read or a write timed out: discard this connection
                logger.debug("Request timed out: %r", e)
                self.close_connection = True

        def _read_head(self, line: bytes) -> None:
            """Parse the request line and read the header lines: sets
            ``command``, ``path`` (the target as sent), ``headers`` (the
            received spelling of each name to its value, the first of a
            repeated name; handed to ``dispatch`` as it is), ``_length``,
            ``_content_type``, ``_chunked`` and ``close_connection``, and
            answers ``Expect: 100-continue``. Raises :class:`_Refused`."""
            self._line = line
            if len(line) > _MAX_LINE:
                raise _Refused(414, "Request line too long.")
            words = line.split()
            if len(words) != 3:
                raise _Refused(400, "Malformed request line.")
            method, target, version = words
            keep_alive = http11 = _keeps_alive(version)
            command = method.decode("latin-1")
            if command not in _METHODS:
                raise _Refused(501, f"Unsupported method ({command[:32]!r}).")
            if target[:2] == b"//":
                # gh-87389: clients read //path as a URI without a scheme
                target = b"/" + target.lstrip(b"/")
            self.command = command
            self.path = target.decode("latin-1")

            headers: dict[str, str] = {}
            low: dict[str, str] = {}  # the same values under lowered names
            readline = self.rfile.readline
            lines = 0
            while True:
                line = readline(_MAX_LINE + 1)
                if len(line) > _MAX_LINE:
                    raise _Refused(431, "Header line too long.")
                lines += 1
                if lines > _MAX_HEADER_LINES:
                    raise _Refused(431, "Too many headers.")
                if line in (b"\r\n", b"\n", b""):
                    break
                name, colon, value = line.decode("latin-1").partition(":")
                if not colon or not name or name[0] in " \t" or name[-1] in " \t":
                    # no name, a folded line (RFC 7230 3.2.4 lets a server
                    # refuse one) or space before the colon: read leniently,
                    # each hides a header from this parser or from the next
                    raise _Refused(400, "Malformed header line.")
                value = value.strip()
                key = name.lower()
                if key in low:
                    # a repeated name: the first value stands, but a body's
                    # length is never guessed
                    if key == "content-length" and low[key] != value:
                        raise _Refused(400, "Conflicting Content-Length.")
                    headers.setdefault(name, low[key])
                else:
                    low[key] = headers[name] = value
            self.headers = headers

            connection = low.get("connection")
            if connection is not None:
                connection = connection.lower()
                if connection == "close":
                    keep_alive = False
                elif connection == "keep-alive":
                    keep_alive = True
            self.close_connection = not keep_alive
            length = low.get("content-length")
            if length is None:
                self._length = 0
            elif length.isdecimal():
                self._length = int(length)
            else:
                raise _Refused(400, "Content-Length is no number.")
            self._content_type = low.get("content-type", "")
            self._chunked = "chunked" in low.get("transfer-encoding", "").lower()
            if http11 and low.get("expect", "").lower() == "100-continue":
                # the caller holds its body back until it reads this
                self.wfile.write(b"HTTP/1.1 100 Continue\r\n\r\n")
                self.wfile.flush()

        def _refuse(self, status: int, message: str) -> None:
            logger.debug("refused with %d: %s", status, message)
            self._send(
                status,
                json.dumps({"message": message}).encode(),
                extra_headers={"Connection": "close"},
            )

        def finish(self):
            # the connection ends: what is left of a group of riders
            collector = getattr(self, "_collector", None)
            if collector is not None and collector.counted("rider.requests"):
                span_sink((), self._take_riders())
            super().finish()

        def _take_riders(self) -> dict:
            """Close the group of riders this thread has served since the
            last one: one read of the thread's CPU clock, and the
            collector's counts."""
            cpu_ns, before = time.thread_time_ns(), self._cpu_ns
            self._cpu_ns = cpu_ns
            spans.count("rider.cpuNs", cpu_ns - before)
            return self._collector.take_counts()

        def _respond(self):
            target = self.path
            if (
                target[0] == "/"
                and "?" not in target
                and "#" not in target
                and ";" not in target
            ):
                path, params = target, {}
            else:
                parsed = urllib.parse.urlparse(target)
                path = parsed.path
                params = {
                    k: v[0] for k, v in urllib.parse.parse_qs(parsed.query).items()
                }
            # health probes are transport-level (docs/operations.md):
            # answered before service dispatch so every server exposes
            # them uniformly and a wedged service layer cannot take the
            # liveness probe down with it
            if self.command == "GET" and path == "/healthz":
                self._send(200, b'{"status": "ok"}')
                return
            if self.command == "GET" and path == "/readyz":
                self._ready_probe()
                return
            if lifecycle is not None:
                # graceful drain (docs/operations.md): once draining, new
                # work is refused with a clean 503 + Retry-After while
                # requests already admitted run to completion. Admission
                # and the in-flight count are one atomic step, so the
                # drain's idle-wait can never miss a racing request.
                if not lifecycle.try_begin_request():
                    # Connection: close (_send flips close_connection
                    # too): the rejection never reads the request body, so
                    # a kept-alive connection would desync on the unread
                    # bytes — and a draining listener is going away anyway
                    self._send(
                        503,
                        b'{"message": "Server is draining; retry elsewhere."}',
                        extra_headers={
                            "Retry-After": str(lifecycle.retry_after_s()),
                            "Connection": "close",
                        },
                    )
                    return
                try:
                    self._dispatch_and_send(path, params)
                finally:
                    lifecycle.end_request()
                return
            self._dispatch_and_send(path, params)

        def _dispatch_and_send(self, path, params):
            # streaming routes (the bulk-ingest endpoint): the service
            # gets the raw body reader instead of a parsed JSON body, so
            # the payload is consumed incrementally — never materialized
            if stream_routes and (self.command, path) in stream_routes:
                self._dispatch_stream(path, params)
                return
            riders = 0
            if self._collector is not None:
                self._collector.take()  # what an unanswered request left
                riders = self._collector.counted("rider.requests")
            body = None
            form: Mapping[str, str] | None = None
            with spans.span("httpRead"):
                raw = self.rfile.read(self._length) if self._length else b""
                if raw:
                    # Tolerant parse: clients (e.g. bare `curl -d`) often
                    # send JSON under a form-encoded default content type.
                    # Try JSON first for any body; fall back to form fields
                    # only when the payload isn't JSON and the content type
                    # says form.
                    try:
                        body = json.loads(raw)
                    except json.JSONDecodeError:
                        ctype = self._content_type.split(";")[0].strip()
                        if ctype == "application/x-www-form-urlencoded":
                            form = {
                                k: v[0]
                                for k, v in urllib.parse.parse_qs(
                                    raw.decode()
                                ).items()
                            }
                        else:
                            self._send(400, b'{"message": "Malformed JSON."}')
                            return
            try:
                resp = dispatch(
                    method=self.command,
                    path=path,
                    params=params,
                    body=body,
                    headers=self.headers,
                    form=form,
                )
            except Exception:
                logger.exception("Unhandled error for %s %s", self.command, path)
                self._send(500, b'{"message": "Internal Server Error"}')
                return
            with spans.span("httpWrite"):
                self._send(
                    resp.status,
                    resp.json_bytes(),
                    getattr(
                        resp, "content_type", "application/json; charset=UTF-8"
                    ),
                    getattr(resp, "headers", None),
                )
                self.wfile.flush()  # the reply is on the wire
            if span_sink is not None:
                counts = None
                group = self._collector.counted("rider.requests")
                if group > riders:  # this request rode in a batch
                    spans.count(
                        "rider.requestNs",
                        time.perf_counter_ns() - self._request_ns,
                    )
                    if group >= RIDERS_A_CPU_READ:
                        counts = self._take_riders()
                span_sink(self._collector.take(), counts)
            after_send = getattr(resp, "after_send", None)
            if after_send is not None:
                # the reply is flushed before the hook runs: /stop shuts
                # the listener (and the process) down from here
                after_send()

        def _dispatch_stream(self, path, params):
            if self._chunked:
                reader = _ChunkedReader(self.rfile)
            else:
                reader = _LengthReader(self.rfile, self._length)
            try:
                resp = dispatch(
                    method=self.command,
                    path=path,
                    params=params,
                    body=None,
                    headers=self.headers,
                    form=None,
                    stream=reader,
                )
            except Exception:
                logger.exception("Unhandled error for %s %s", self.command, path)
                self._send(500, b'{"message": "Internal Server Error"}')
                self.close_connection = True
                return
            chunks = getattr(resp, "chunks", None)
            if chunks is None:
                # plain Response (auth / validation errors before the
                # body was touched)
                self._send(
                    resp.status,
                    resp.json_bytes(),
                    getattr(resp, "content_type", "application/json; charset=UTF-8"),
                    getattr(resp, "headers", None),
                )
            else:
                self._send_stream(resp, chunks)
            if not reader.exhausted:
                # unread request bytes would desync a kept-alive
                # connection — hang up instead
                self.close_connection = True

        def _send_stream(self, resp, chunks):
            """Chunked-transfer response: each piece goes out (and is
            flushed) the moment the service yields it."""
            self.wfile.write(
                self._head(
                    resp.status,
                    getattr(resp, "content_type", "application/x-ndjson"),
                    "Transfer-Encoding: chunked\r\n",
                    getattr(resp, "headers", None),
                )
            )
            try:
                for piece in chunks:
                    if not piece:
                        continue
                    self.wfile.write(
                        f"{len(piece):X}\r\n".encode("ascii") + piece + b"\r\n"
                    )
                    self.wfile.flush()
                self.wfile.write(b"0\r\n\r\n")
            except Exception:
                # mid-stream failure after a 200 status: the truncated
                # chunked framing is the client's error signal
                logger.exception("streaming response aborted")
                self.close_connection = True

        def _ready_probe(self):
            """GET /readyz: 200 when the service's readiness hook says
            every dependency check passed, 503 otherwise. Servers without
            a hook are ready whenever they are alive. A draining server
            is never ready — the balancer must stop routing here before
            the listener goes away."""
            if lifecycle is not None and lifecycle.draining:
                self._send(503, b'{"ready": false, "draining": true}')
                return
            if readiness is None:
                self._send(200, b'{"ready": true, "checks": {}}')
                return
            try:
                report = dict(readiness())
            except Exception as e:
                logger.exception("readiness hook failed")
                report = {"ready": False, "error": str(e)[:200]}
            status = 200 if report.get("ready") else 503
            self._send(status, json.dumps(report, default=str).encode())

        def _head(
            self,
            status: int,
            content_type: str,
            framing: str,
            extra_headers: Mapping[str, str] | None,
        ) -> bytes:
            """An answer's head, the blank line included: the status line,
            ``Server``, ``Date``, ``Content-Type``, the ``framing`` line
            (how the body ends), the extra headers in their order. An
            extra ``Connection`` header decides, as a request's does,
            whether the connection outlives the answer."""
            lines = f"Content-Type: {content_type}\r\n{framing}"
            if extra_headers:
                for name, value in extra_headers.items():
                    lines += f"{name}: {value}\r\n"
                    if name.lower() == "connection":
                        value = value.lower()
                        if value == "close":
                            self.close_connection = True
                        elif value == "keep-alive":
                            self.close_connection = False
            if logger.isEnabledFor(logging.DEBUG):
                logger.debug(
                    '%s - "%s" %d',
                    self.client_address[0],
                    self._line.decode("latin-1").rstrip("\r\n"),
                    status,
                )
            lines += "\r\n"
            status_line = _STATUS_LINES.get(status)
            if status_line is None:  # a status the standard library has no phrase for
                status_line = f"HTTP/1.1 {status} \r\n".encode("latin-1")
            return status_line + _server_and_date() + lines.encode("latin-1")

        def _send(
            self,
            status: int,
            payload: bytes,
            content_type: str = "application/json; charset=UTF-8",
            extra_headers: Mapping[str, str] | None = None,
        ):
            # head and body in one write
            self.wfile.write(
                self._head(
                    status,
                    content_type,
                    f"Content-Length: {len(payload)}\r\n",
                    extra_headers,
                )
                + payload
            )

    return Handler


class _Server(ThreadingHTTPServer):
    #: listen(2) backlog. http.server's default of 5 overflows the SYN
    #: queue the moment a few dozen clients connect at once (measured:
    #: 1 s / 3 s latency cliffs from kernel SYN retransmission plus
    #: outright connection resets at concurrency 32); serving millions
    #: of users means absorbing connect storms at the accept queue.
    request_queue_size = 128


def _resolve_drain_hook(dispatch: Dispatcher) -> Callable[[], None] | None:
    """A service object's ``drain`` method, discovered from a bound
    ``dispatch`` the same way readiness is — so the query server's
    micro-batcher close (``QueryService.drain``) runs in the drain
    sequence without per-server wiring."""
    owner = getattr(dispatch, "__self__", None)
    hook = getattr(owner, "drain", None)
    return hook if callable(hook) else None


def _make_server(
    dispatch: Dispatcher,
    host: str,
    port: int,
    ssl_context: ssl.SSLContext | None,
    readiness: ReadinessHook | None = None,
    lifecycle: "DrainManager | None" = None,
) -> ThreadingHTTPServer:
    handler = _make_handler(
        dispatch, _resolve_readiness(dispatch, readiness), lifecycle
    )
    server = _Server((host, port), handler)
    if lifecycle is not None:
        lifecycle.attach_server(server)
        drain_hook = _resolve_drain_hook(dispatch)
        if drain_hook is not None:
            # ahead of any process-level hooks (storage flush): the
            # service must release its own machinery first
            lifecycle.add_drain_hook(drain_hook, first=True)
    if ssl_context is not None:
        # defer the handshake to the per-connection worker thread: with
        # do_handshake_on_connect=True it would run inside accept() on
        # the serve_forever thread, letting ONE stalled client block the
        # whole server. Lazily it runs on first read under the handler's
        # socket timeout instead.
        server.socket = ssl_context.wrap_socket(
            server.socket, server_side=True, do_handshake_on_connect=False
        )
    return server


def serve(
    dispatch: Dispatcher,
    host: str = "0.0.0.0",
    port: int = 7070,
    ssl_context: ssl.SSLContext | None = None,
    ready_callback: Callable[[ThreadingHTTPServer], None] | None = None,
    readiness: ReadinessHook | None = None,
    lifecycle: "DrainManager | None" = None,
) -> None:
    """Blocking serve-forever (used by ``pio eventserver`` / ``pio deploy``).

    ``ready_callback`` receives the bound server before requests flow —
    deploy uses it to wire the ``GET /stop`` shutdown hook. ``readiness``
    backs ``GET /readyz`` (defaults to the service's own ``readiness``
    method when ``dispatch`` is a bound method). ``lifecycle`` (opt-in,
    ``--drain-deadline-s``) enables graceful signal-driven drain; without
    it signal behavior is the historical immediate exit."""
    server = _make_server(dispatch, host, port, ssl_context, readiness, lifecycle)
    logger.info(
        "Listening on %s://%s:%d",
        "https" if ssl_context else "http", host, port,
    )
    if ready_callback is not None:
        ready_callback(server)
    try:
        server.serve_forever()
    finally:
        server.server_close()


def start_background(
    dispatch: Dispatcher,
    host: str = "127.0.0.1",
    port: int = 0,
    ssl_context: ssl.SSLContext | None = None,
    readiness: ReadinessHook | None = None,
    lifecycle: "DrainManager | None" = None,
) -> tuple[ThreadingHTTPServer, threading.Thread]:
    """Start on a daemon thread; returns (server, thread). ``port=0`` picks
    a free port (``server.server_address[1]``). Used by tests and the
    feedback loop."""
    server = _make_server(dispatch, host, port, ssl_context, readiness, lifecycle)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, thread
