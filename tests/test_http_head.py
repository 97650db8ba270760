"""The transport's own head code (``api/http.py``), held on raw sockets to
what ``http.server`` did before it: how a request's head is read (names in
any case, keep-alive rules, ``Expect``, the limits and their statuses) and
how an answer's head is written (byte for byte). The server is its own
only parser, so every rule a client can lean on has a case here."""

import datetime as dt
import json
import logging
import re
import shutil
import socket
import ssl
import subprocess
import sys
import threading
import time
from email.utils import parsedate_to_datetime
from http import HTTPStatus

import pytest

from predictionio_tpu.api import http as pio_http
from predictionio_tpu.api.http import make_ssl_context, start_background


class _Resp:
    def __init__(self, status=200, payload=None, headers=None, content_type=None):
        self.status = status
        self._payload = payload if payload is not None else {"ok": True}
        if headers is not None:
            self.headers = headers
        if content_type is not None:
            self.content_type = content_type

    def json_bytes(self):
        return json.dumps(self._payload).encode()


class _Chunks:
    status = 200
    content_type = "application/x-ndjson"
    headers = {"X-Stream": "yes"}

    def __init__(self, chunks):
        self.chunks = chunks


class _Service:
    """A trivial service: answers with what it was handed, so a test reads
    from the answer what reached ``dispatch``."""

    stream_routes = frozenset({("POST", "/stream")})

    def __init__(self):
        self.seen = []

    def dispatch(self, method, path, params, body, headers, form, stream=None):
        self.seen.append((method, path))
        if stream is not None:
            data = b""
            while True:
                piece = stream.read(7)
                if not piece:
                    break
                data += piece
            return _Chunks([data[:5], b"", data[5:], b"|end"])
        if path == "/boom":
            raise RuntimeError("boom")
        if path == "/status":
            return _Resp(
                int(params["code"]),
                headers={"X-First": "1", "X-Second": "two"},
                content_type="text/plain",
            )
        if path == "/bye":
            return _Resp(headers={"Connection": "close"})
        return _Resp(
            payload={
                "method": method,
                "path": path,
                "params": params,
                "body": body,
                "headers": headers,
                "form": form,
            }
        )


@pytest.fixture(scope="module")
def server():
    service = _Service()
    srv, _ = start_background(service.dispatch)
    yield srv.server_address[1], service
    srv.shutdown()
    srv.server_close()


def _connect(port):
    return socket.create_connection(("127.0.0.1", port), timeout=10)


def _read_answer(reader):
    """One answer off a buffered reader: (status line, headers as a list of
    (name, value) in order, body). ``None`` at a closed connection."""
    status_line = reader.readline()
    if not status_line:
        return None
    headers = []
    while True:
        line = reader.readline()
        assert line, "connection closed inside a head"
        if line == b"\r\n":
            break
        name, _, value = line.decode("latin-1").rstrip("\r\n").partition(": ")
        headers.append((name, value))
    found = dict(headers)
    if found.get("Transfer-Encoding") == "chunked":
        body = b""
        while True:
            size = int(reader.readline().strip(), 16)
            if size == 0:
                assert reader.readline() == b"\r\n"
                break
            body += reader.read(size)
            assert reader.read(2) == b"\r\n"
    else:
        body = reader.read(int(found.get("Content-Length", 0)))
    return status_line, headers, body


def _exchange(port, raw, answers=1):
    """Send ``raw`` in one ``sendall``; the answers read, then whether the
    server closed the connection after them: a probe sent on it is either
    answered or meets the end of the stream, so nothing waits on a clock."""
    with _connect(port) as sock, sock.makefile("rb") as reader:
        sock.sendall(raw)
        got = [_read_answer(reader) for _ in range(answers)]
        try:
            sock.sendall(b"GET /healthz HTTP/1.1\r\n\r\n")
            closed = _read_answer(reader) is None
        except (ConnectionResetError, BrokenPipeError):
            closed = True
    return got, closed


def _post(path, body=b'{"a": 1}', version="HTTP/1.1", extra=()):
    lines = [f"POST {path} {version}", "Host: t", *extra,
             f"Content-Length: {len(body)}"]
    return "\r\n".join(lines).encode("latin-1") + b"\r\n\r\n" + body


# -- reading the head ------------------------------------------------------


@pytest.mark.parametrize(
    "length_name, type_name, probe_name",
    [
        ("Content-Length", "Content-Type", "X-Probe"),
        ("content-length", "content-type", "x-probe"),
        ("CONTENT-LENGTH", "CONTENT-TYPE", "X-PROBE"),
        ("cOnTeNt-LeNgTh", "cOnTeNt-TyPe", "x-PrObE"),
    ],
)
def test_header_names_in_any_case(server, length_name, type_name, probe_name):
    port, _ = server
    # the body is no JSON: only a form content type found in any case
    # turns it into form fields, and only a length found reads it at all
    body = b"k=v&n=2"
    raw = (
        f"POST /echo HTTP/1.1\r\nHost: t\r\n{probe_name}:  spaced value \r\n"
        f"{type_name}: application/x-www-form-urlencoded; charset=x\r\n"
        f"{length_name}: {len(body)}\r\n\r\n"
    ).encode() + body
    [(status_line, _, answer)], closed = _exchange(port, raw)
    assert status_line == b"HTTP/1.1 200 OK\r\n"
    assert not closed
    echoed = json.loads(answer)
    assert echoed["form"] == {"k": "v", "n": "2"}
    # the received spelling reaches dispatch, the value trimmed
    assert echoed["headers"][probe_name] == "spaced value"
    assert echoed["headers"][length_name] == str(len(body))
    assert set(echoed["headers"]) == {"Host", probe_name, type_name, length_name}


def test_repeated_header_keeps_its_first_value(server):
    port, _ = server
    raw = _post("/echo", extra=("X-Twice: one", "X-Twice: two", "x-twice: three"))
    [(_, _, answer)], _ = _exchange(port, raw)
    headers = json.loads(answer)["headers"]
    # under either spelling, as ``dict(email.message.Message)`` gave it
    assert headers["X-Twice"] == "one" and headers["x-twice"] == "one"


def test_three_pipelined_requests_get_three_answers_in_order(server):
    port, _ = server
    raw = b"".join(_post(f"/echo/{i}", body=json.dumps({"i": i}).encode())
                   for i in range(3))
    answers, closed = _exchange(port, raw, answers=3)
    assert [json.loads(a[2])["path"] for a in answers] == [
        "/echo/0", "/echo/1", "/echo/2"]
    assert [json.loads(a[2])["body"] for a in answers] == [
        {"i": 0}, {"i": 1}, {"i": 2}]
    assert not closed


@pytest.mark.parametrize(
    "version, connection, closes",
    [
        ("HTTP/1.1", None, False),
        ("HTTP/1.1", "close", True),
        ("HTTP/1.1", "Close", True),
        ("HTTP/1.1", "keep-alive", False),
        ("HTTP/1.0", None, True),
        ("HTTP/1.0", "keep-alive", False),
        ("HTTP/1.0", "Keep-Alive", False),
        ("HTTP/1.0", "close", True),
        ("HTTP/1.2", None, False),
        ("HTTP/01.01", None, False),
    ],
)
def test_keep_alive_rules(server, version, connection, closes):
    port, _ = server
    extra = (f"Connection: {connection}",) if connection else ()
    [(status_line, _, _)], closed = _exchange(
        port, _post("/echo", version=version, extra=extra))
    # the answer's own version never follows the request's
    assert status_line == b"HTTP/1.1 200 OK\r\n"
    assert closed is closes


def test_an_answers_own_connection_close_ends_the_connection(server):
    port, _ = server
    [(_, headers, _)], closed = _exchange(port, _post("/bye"))
    assert ("Connection", "close") in headers
    assert closed


@pytest.mark.parametrize(
    "version, expect, continued",
    [
        ("HTTP/1.1", "100-continue", True),
        ("HTTP/1.1", "100-Continue", True),
        ("HTTP/1.0", "100-continue", False),
    ],
)
def test_expect_100_continue_is_answered_before_the_body(
        server, version, expect, continued):
    port, _ = server
    body = b'{"late": true}'
    head = (
        f"POST /echo {version}\r\nHost: t\r\nExpect: {expect}\r\n"
        f"Connection: keep-alive\r\nContent-Length: {len(body)}\r\n\r\n"
    ).encode()
    with _connect(port) as sock, sock.makefile("rb") as reader:
        sock.sendall(head)
        if continued:
            # nothing of the body is on the wire yet
            assert reader.readline() == b"HTTP/1.1 100 Continue\r\n"
            assert reader.readline() == b"\r\n"
        else:
            sock.settimeout(0.3)
            with pytest.raises((socket.timeout, TimeoutError)):
                sock.recv(1)
            sock.settimeout(10)
        sock.sendall(body)
        status_line, _, answer = _read_answer(reader)
    assert status_line == b"HTTP/1.1 200 OK\r\n"
    assert json.loads(answer)["body"] == {"late": True}


_LONG = 65536


@pytest.mark.parametrize(
    "raw, status",
    [
        (b"GET\r\n\r\n", 400),
        (b"\r\n", 400),
        (b"GET /healthz\r\n\r\n", 400),  # HTTP/0.9: an answer without a head
        (b"GET /healthz HTTP/0.9\r\n\r\n", 400),
        (b"GET /healthz HTTP/1.1 more\r\n\r\n", 400),
        (b"GET /healthz HTPP/1.1\r\n\r\n", 400),
        (b"GET /healthz HTTP/1.x\r\n\r\n", 400),
        (b"GET /healthz HTTP/1.1.1\r\n\r\n", 400),
        (b"GET /healthz HTTP/1.12345678901\r\n\r\n", 400),
        (b"GET /healthz HTTP/2.0\r\n\r\n", 505),
        (b"PATCH /healthz HTTP/1.1\r\n\r\n", 501),
        (b"HEAD /healthz HTTP/1.1\r\n\r\n", 501),
        # a line longer than 65,536 bytes: nothing of it is left unread
        (b"GET /" + b"a" * (_LONG + 1 - 5), 414),
        (b"GET / HTTP/1.1\r\nX-Long: " + b"a" * (_LONG + 1 - 8), 431),
        (b"GET / HTTP/1.1\r\n" + b"".join(
            b"X-%d: v\r\n" % i for i in range(100)) + b"\r\n", 431),
        (b"GET / HTTP/1.1\r\nno colon here\r\n\r\n", 400),
        (b"GET / HTTP/1.1\r\n: no name\r\n\r\n", 400),
        (b"GET / HTTP/1.1\r\nX-Fold: a\r\n  folded\r\n\r\n", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length : 2\r\n\r\n{}", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length: two\r\n\r\n{}", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length: -2\r\n\r\n{}", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length: +2\r\n\r\n{}", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length: 2, 2\r\n\r\n{}", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length: \xb2\r\n\r\n{}", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length: 2\r\n"
         b"Content-Length: 3\r\n\r\n{} ", 400),
        (b"POST /echo HTTP/1.1\r\nContent-Length: 2\r\n"
         b"content-length: 02\r\n\r\n{}", 400),
    ],
)
def test_a_head_the_server_does_not_serve_is_refused_and_the_connection_closed(
        server, raw, status):
    port, service = server
    seen = len(service.seen)
    [(status_line, headers, answer)], closed = _exchange(port, raw)
    phrase = HTTPStatus(status).phrase
    assert status_line == f"HTTP/1.1 {status} {phrase}\r\n".encode()
    assert ("Connection", "close") in headers
    assert json.loads(answer)["message"]
    assert closed
    assert len(service.seen) == seen  # nothing reached the service


@pytest.mark.parametrize(
    "raw",
    [
        # the limits themselves are served
        b"GET /" + b"a" * (_LONG - 16) + b" HTTP/1.1\r\n\r\n",
        b"GET /echo HTTP/1.1\r\nX-Long: " + b"a" * (_LONG - 10) + b"\r\n\r\n",
        b"GET /echo HTTP/1.1\r\n" + b"".join(
            b"X-%d: v\r\n" % i for i in range(99)) + b"\r\n",
        # the same length said twice is no conflict
        b"POST /echo HTTP/1.1\r\nContent-Length: 2\r\ncontent-length: 2\r\n\r\n{}",
        # a bare LF ends a line as well
        b"POST /echo HTTP/1.1\nContent-Length: 2\n\n{}",
    ],
)
def test_a_head_at_the_limits_is_served(server, raw):
    port, _ = server
    [(status_line, _, _)], closed = _exchange(port, raw)
    assert status_line == b"HTTP/1.1 200 OK\r\n"
    assert not closed


@pytest.mark.parametrize(
    "target, path, params",
    [
        ("/echo", "/echo", {}),
        ("/echo?a=1&b=two&a=3", "/echo", {"a": "1", "b": "two"}),
        ("/echo?", "/echo", {}),
        ("//echo//x", "/echo//x", {}),
        ("http://host:80/echo?k=v", "/echo", {"k": "v"}),
        ("/echo#frag", "/echo", {}),
        ("/echo;p=1", "/echo", {}),
    ],
)
def test_the_target_reaches_dispatch_as_path_and_params(server, target, path, params):
    port, _ = server
    [(_, _, answer)], _ = _exchange(
        port, f"GET {target} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    echoed = json.loads(answer)
    assert (echoed["path"], echoed["params"]) == (path, params)


@pytest.mark.parametrize("probe", ["/healthz", "/readyz", "/healthz?x=1"])
def test_probes_are_answered_before_dispatch(server, probe):
    port, service = server
    seen = len(service.seen)
    [(status_line, _, _)], _ = _exchange(
        port, f"GET {probe} HTTP/1.1\r\n\r\n".encode())
    assert status_line == b"HTTP/1.1 200 OK\r\n"
    assert len(service.seen) == seen


def test_a_body_that_is_no_json_is_a_400_and_the_connection_lives(server):
    port, _ = server
    raw = _post("/echo", body=b"not json") + _post("/echo")
    answers, closed = _exchange(port, raw, answers=2)
    assert [a[0] for a in answers] == [
        b"HTTP/1.1 400 Bad Request\r\n", b"HTTP/1.1 200 OK\r\n"]
    assert not closed


def test_a_dispatch_that_raises_is_a_500(server):
    port, _ = server
    [(status_line, _, answer)], _ = _exchange(port, _post("/boom"))
    assert status_line == b"HTTP/1.1 500 Internal Server Error\r\n"
    assert json.loads(answer) == {"message": "Internal Server Error"}


# -- writing the head ------------------------------------------------------


_SERVER = f"BaseHTTP/0.6 Python/{sys.version.split()[0]}"


def _assert_dated_now(value):
    assert re.fullmatch(
        r"(Mon|Tue|Wed|Thu|Fri|Sat|Sun), \d\d "
        r"(Jan|Feb|Mar|Apr|May|Jun|Jul|Aug|Sep|Oct|Nov|Dec) \d{4} "
        r"\d\d:\d\d:\d\d GMT", value), value
    when = parsedate_to_datetime(value)
    assert abs((dt.datetime.now(dt.timezone.utc) - when).total_seconds()) <= 2


@pytest.mark.parametrize(
    "code, phrase",
    [
        (200, "OK"),
        (201, "Created"),
        (404, "Not Found"),
        (429, "Too Many Requests"),
        (503, "Service Unavailable"),
        (299, ""),  # no phrase in http.HTTPStatus: an empty one, as before
    ],
)
def test_the_answers_head_byte_for_byte(server, code, phrase):
    port, _ = server
    with _connect(port) as sock:
        sock.sendall(f"GET /status?code={code} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        raw = b""
        while b"\r\n\r\n" not in raw:
            raw += sock.recv(65536)
        head, _, body = raw.partition(b"\r\n\r\n")
        payload = json.dumps({"ok": True}).encode()
        while len(body) < len(payload):
            body += sock.recv(65536)
    assert body == payload
    lines = head.decode("latin-1").split("\r\n")
    date = lines[2]
    assert date.startswith("Date: ")
    _assert_dated_now(date[len("Date: "):])
    assert lines[:2] + lines[3:] == [
        f"HTTP/1.1 {code} {phrase}",
        f"Server: {_SERVER}",
        "Content-Type: text/plain",
        f"Content-Length: {len(payload)}",
        "X-First: 1",
        "X-Second: two",
    ]


def test_the_date_is_made_once_a_second_and_shared(monkeypatch):
    made = []
    real = pio_http.formatdate

    def counting(second, usegmt):
        made.append(second)
        return real(second, usegmt=usegmt)

    clock = [1_700_000_000.25]
    monkeypatch.setattr(pio_http, "formatdate", counting)
    monkeypatch.setattr(pio_http.time, "time", lambda: clock[0])
    monkeypatch.setattr(pio_http, "_dated", (0, b""))
    first = pio_http._server_and_date()
    clock[0] += 0.5
    assert pio_http._server_and_date() is first
    clock[0] += 0.5
    later = pio_http._server_and_date()
    assert made == [1_700_000_000, 1_700_000_001]
    assert first == (
        f"Server: {_SERVER}\r\nDate: Tue, 14 Nov 2023 22:13:20 GMT\r\n".encode())
    assert later.endswith(b"Date: Tue, 14 Nov 2023 22:13:21 GMT\r\n")


def test_threads_racing_for_the_date_each_read_a_whole_one():
    """More threads than cores on a short switch interval: whichever of
    the pair a thread reads, it is a whole ``Server`` + ``Date``."""
    bad, stop = [], time.monotonic() + 0.5
    before = sys.getswitchinterval()

    def reader():
        while time.monotonic() < stop:
            lines = pio_http._server_and_date().decode("latin-1").split("\r\n")
            when = parsedate_to_datetime(lines[1][len("Date: "):])
            late = (dt.datetime.now(dt.timezone.utc) - when).total_seconds()
            if lines[0] != f"Server: {_SERVER}" or lines[2] or not -1 <= late <= 2.5:
                bad.append(lines)

    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    assert bad == []


def test_a_request_is_logged_only_at_debug(server, caplog):
    port, _ = server
    with caplog.at_level(logging.INFO, logger=pio_http.logger.name):
        _exchange(port, _post("/echo/quiet"))
    assert caplog.records == []
    with caplog.at_level(logging.DEBUG, logger=pio_http.logger.name):
        _exchange(port, _post("/echo/loud"))
    assert '127.0.0.1 - "POST /echo/loud HTTP/1.1" 200' in [
        r.getMessage() for r in caplog.records]


# -- the routes that bypass the JSON body ----------------------------------


@pytest.mark.parametrize("chunked", [True, False])
def test_a_stream_route_passes_through_the_same_head_code(server, chunked):
    port, _ = server
    if chunked:
        framing = "transfer-encoding: Chunked\r\n"
        body = b"5\r\nhello\r\n6;ext=1\r\n world\r\n0\r\n\r\n"
    else:
        framing = "Content-Length: 11\r\n"
        body = b"hello world"
    raw = f"POST /stream HTTP/1.1\r\nHost: t\r\n{framing}\r\n".encode() + body
    answers, closed = _exchange(port, raw + _post("/echo"), answers=2)
    status_line, headers, answer = answers[0]
    assert status_line == b"HTTP/1.1 200 OK\r\n"
    assert [name for name, _ in headers] == [
        "Server", "Date", "Content-Type", "Transfer-Encoding", "X-Stream"]
    found = dict(headers)
    assert found["Server"] == _SERVER
    _assert_dated_now(found["Date"])
    assert found["Content-Type"] == "application/x-ndjson"
    assert answer == b"hello world|end"
    # the body was read to its end: the connection serves the next request
    assert answers[1][0] == b"HTTP/1.1 200 OK\r\n"
    assert not closed


@pytest.fixture(scope="module")
def cert_pair(tmp_path_factory):
    if not shutil.which("openssl"):
        pytest.skip("no openssl binary to make a certificate with")
    d = tmp_path_factory.mktemp("certs")
    cert, key = d / "server.crt", d / "server.key"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-keyout", str(key),
         "-out", str(cert), "-days", "1", "-nodes", "-subj", "/CN=localhost"],
        check=True, capture_output=True)
    return str(cert), str(key)


def test_a_tls_exchange_passes_through_the_same_head_code(cert_pair):
    service = _Service()
    srv, _ = start_background(
        service.dispatch, ssl_context=make_ssl_context(*cert_pair))
    ctx = ssl.create_default_context()
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE
    try:
        with socket.create_connection(srv.server_address, timeout=10) as plain:
            with ctx.wrap_socket(plain, server_hostname="localhost") as sock:
                with sock.makefile("rb") as reader:
                    sock.sendall(_post("/echo/1") + _post("/echo/2"))
                    first, second = _read_answer(reader), _read_answer(reader)
                    sock.sendall(b"GET / HTTP/1.1\r\nbroken\r\n\r\n")
                    refused = _read_answer(reader)
                    assert reader.read(1) == b""
    finally:
        srv.shutdown()
        srv.server_close()
    assert [json.loads(a[2])["path"] for a in (first, second)] == [
        "/echo/1", "/echo/2"]
    assert dict(first[1])["Server"] == _SERVER
    assert refused[0] == b"HTTP/1.1 400 Bad Request\r\n"
