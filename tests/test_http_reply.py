"""Replies of the JSON handlers (``serve_api`` and the fleet manager):
each leaves in one write, with the bytes that the headers-then-body
sequence sent, and a keep-alive connection carries on after an error."""
import http.client
import json
import socketserver
import tempfile
import time

import pytest

from repro.api import CreateExperiment, LocalClient, serve_api
from repro.api.http import JsonHandler
from repro.core import ExperimentConfig, Param, Space
from repro.core import tracing
from repro.fleet import serve as fleet_serve

ID, PARENT, REQUEST, NAME, THREAD, START, END, ATTRS = range(8)
DATE = "Sun, 18 Oct 2026 00:00:00 GMT"


@pytest.fixture
def writes(monkeypatch):
    """Every write a handler makes on its socket, as (server port, bytes);
    a handler's unbuffered ``wfile`` makes one ``sendall`` per write."""
    seen = []
    write = socketserver._SocketWriter.write

    def recording(self, b):
        seen.append((self._sock.getsockname()[1], bytes(b)))
        return write(self, b)

    monkeypatch.setattr(socketserver._SocketWriter, "write", recording)
    return seen


@pytest.fixture
def api():
    backend = LocalClient(tempfile.mkdtemp())
    cfg = ExperimentConfig(name="reply", budget=8, parallel=2,
                           optimizer="random",
                           space=Space([Param("x", "double", 0, 1)]))
    exp = backend.create_experiment(
        CreateExperiment(config=cfg.to_json())).exp_id
    server = serve_api(backend).start()
    try:
        yield server, exp
    finally:
        server.shutdown()


def _call(conn, method, path, body=None):
    """-> (status, headers, raw body) read back by ``http.client``."""
    if isinstance(body, dict):
        body = json.dumps(body).encode()
    conn.request(method, path, body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, resp.headers, resp.read()


def _check_reply(status, headers, raw, want_status):
    assert status == want_status
    assert headers["Content-Type"] == "application/json"
    assert int(headers["Content-Length"]) == len(raw)
    return json.loads(raw)


# (method, path, body, status, what the JSON body holds)
API_CASES = {
    "ok": ("POST", "/v1/experiments/{exp}/suggestions", {"count": 1}, 200,
           lambda j: len(j["suggestions"]) == 1),
    "unknown_experiment": ("GET", "/v1/experiments/missing", None, 404,
                           lambda j: j["error"]["code"]
                           == "unknown_experiment"),
    "bad_json": ("POST", "/v1/experiments/{exp}/suggestions", b"{nope",
                 400, lambda j: j["error"]["code"] == "bad_request"),
    "internal": ("GET", "/v1/load", None, 500,
                 lambda j: j["error"]["code"] == "internal"
                 and "RuntimeError: boom" in j["error"]["message"]),
}


def _boom():
    raise RuntimeError("boom")


@pytest.mark.parametrize("case", sorted(API_CASES))
def test_api_reply_leaves_in_one_write(api, writes, monkeypatch, case):
    server, exp = api
    method, path, body, want, holds = API_CASES[case]
    monkeypatch.setattr(server.backend, "load", _boom)
    conn = http.client.HTTPConnection(server.host, server.port, timeout=10)
    try:
        status, headers, raw = _call(conn, method, path.format(exp=exp),
                                     body)
    finally:
        conn.close()
    assert holds(_check_reply(status, headers, raw, want))
    mine = [b for port, b in writes if port == server.port]
    assert len(mine) == 1
    assert mine[0].startswith(b"HTTP/1.1 %d " % want)
    assert mine[0].endswith(b"\r\n\r\n" + raw)


@pytest.mark.parametrize("first", [
    ("POST", "/v1/experiments/{exp}/bogus", {"pad": "x" * 64}, 400),
    ("POST", "/v1/experiments/missing/suggestions", {"count": 1}, 404)],
    ids=["bad_route_body_unread", "unknown_experiment"])
def test_keep_alive_answers_after_an_error(api, writes, first):
    """An error reply, then a suggest on the same connection: both are
    answered, on the one socket, one write each."""
    server, exp = api
    method, path, body, want = first
    conn = http.client.HTTPConnection(server.host, server.port, timeout=10)
    try:
        err = _check_reply(*_call(conn, method, path.format(exp=exp), body),
                           want)
        sock = conn.sock
        ok = _check_reply(*_call(conn, "POST",
                                 f"/v1/experiments/{exp}/suggestions",
                                 {"count": 2}), 200)
        assert conn.sock is sock is not None
    finally:
        conn.close()
    assert err["error"]["code"] in ("bad_request", "unknown_experiment")
    assert len(ok["suggestions"]) == 2
    assert len([b for port, b in writes if port == server.port]) == 2


def test_http_write_span_per_reply(api, writes, monkeypatch):
    """With tracing on, each reply records one ``http.write`` span, under
    its request's ``http.request``, naming the bytes written."""
    server, exp = api
    monkeypatch.setattr(server.backend, "load", _boom)
    tracing.enable()
    conn = http.client.HTTPConnection(server.host, server.port, timeout=10)
    try:
        for case in ("ok", "unknown_experiment", "internal"):
            method, path, body, want, _ = API_CASES[case]
            assert _call(conn, method, path.format(exp=exp), body)[0] == want
        # the handler closes its spans after the client has read the reply
        recs, deadline = [], time.monotonic() + 10
        while sum(r[NAME] == "http.request" for r in recs) < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
            recs += tracing.drain()
    finally:
        conn.close()
        tracing.disable()
        tracing.drain()
    by_id = {r[ID]: r for r in recs}
    spans = sorted((r for r in recs if r[NAME] == "http.write"),
                   key=lambda r: r[START])
    sent = [b for port, b in writes if port == server.port]
    assert [s[ATTRS] for s in spans] == [{"bytes": len(b)} for b in sent]
    for s in spans:
        req = by_id[s[PARENT]]
        assert req[NAME] == "http.request" and s[REQUEST] == req[ID]
        assert req[THREAD] == s[THREAD]
        assert req[START] <= s[START] <= s[END] <= req[END]


class _Wfile:
    def __init__(self):
        self.writes = []

    def write(self, b):
        self.writes.append(bytes(b))
        return len(b)


def _stub(version):
    h = JsonHandler.__new__(JsonHandler)
    h.request_version = version
    h.requestline = f"GET /v1/healthz {version}".strip()
    h.command = "GET"
    h.wfile = _Wfile()
    h.date_time_string = lambda timestamp=None: DATE
    return h


@pytest.mark.parametrize("version", ["HTTP/1.1", "HTTP/1.0", "HTTP/0.9"])
@pytest.mark.parametrize("status,payload", [
    (200, {"ok": True, "version": "v1", "text": "é"}),
    (404, {"error": {"code": "unknown_experiment", "message": "missing"}}),
    (500, {"error": {"code": "internal", "message": "RuntimeError: boom"}})])
def test_one_write_holds_the_bytes_of_headers_then_body(version, status,
                                                        payload):
    """Byte for byte what ``end_headers()`` and a body write sent."""
    old = _stub(version)
    body = json.dumps(payload).encode()
    old.send_response(status)
    old.send_header("Content-Type", "application/json")
    old.send_header("Content-Length", str(len(body)))
    old.end_headers()
    old.wfile.write(body)
    new = _stub(version)
    new._send(status, payload)
    assert new.wfile.writes == [b"".join(old.wfile.writes)]
    assert not getattr(new, "_headers_buffer", [])


FLEET_CASES = {
    "ok": ("GET", "/fleet/healthz", 200, lambda j: j["ok"]),
    "bad_route": ("POST", "/fleet/bogus", 400,
                  lambda j: j["error"]["code"] == "bad_request"),
    "internal": ("GET", "/fleet/status", 500,
                 lambda j: j["error"]["code"] == "internal"),
}


@pytest.mark.parametrize("case", sorted(FLEET_CASES))
def test_fleet_reply_leaves_in_one_write(writes, monkeypatch, case):
    assert fleet_serve._FleetHandler._send is JsonHandler._send
    srv = fleet_serve.serve_fleet(tempfile.mkdtemp(), shards=1,
                                  period=0.2).start()
    try:
        monkeypatch.setattr(srv.manager, "status", _boom)
        method, path, want, holds = FLEET_CASES[case]
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=10)
        try:
            status, headers, raw = _call(conn, method, path, b"{}")
        finally:
            conn.close()
    finally:
        srv.shutdown()
    assert holds(_check_reply(status, headers, raw, want))
    mine = [b for port, b in writes if port == srv.port]
    assert len(mine) == 1 and mine[0].endswith(b"\r\n\r\n" + raw)
