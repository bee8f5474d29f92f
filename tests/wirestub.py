"""Stand-ins shared by the front door's tests: a raw GKW1 backend with
scripted replies, an HTTP /readyz responder for the readmission prober,
and the client helpers that drive the door."""

import http.client
import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from gatekeeper_tpu.fleet import wireproto


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_until(cond, timeout_s=5.0, step_s=0.02):
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(step_s)
    return cond()


def envelope_for(body: bytes, served_by: str = "") -> bytes:
    try:
        uid = json.loads(body).get("request", {}).get("uid", "")
    except ValueError:
        uid = ""
    return json.dumps({
        "apiVersion": "admission.k8s.io/v1beta1",
        "kind": "AdmissionReview",
        "served_by": served_by,
        "response": {"uid": uid, "allowed": True,
                     "status": {"message": "", "code": 200}},
    }).encode()


class StubWire:
    """Raw wire-protocol backend with scripted reply behaviour.

    mode='echo'    — reply to each chunk in order, one response chunk
    mode='reverse' — reply to the records of each chunk in REVERSE
                     order, one record per response chunk (forces the
                     door to re-order for the client)
    mode='hang'    — never reply
    mode='gate'    — hold every reply until ``gate`` is set (a wedged or
                     slow replica), then behave as 'echo'

    ``delay_s`` sleeps before each reply; ``port`` rebinds a known port
    (a replica coming back where it was).  Every answer names the stub
    (``served_by``) so a test can tell which backend served."""

    def __init__(self, mode: str = "echo", name: str = "stub",
                 port: int = 0, delay_s: float = 0.0):
        self.mode = mode
        self.name = name
        self.delay_s = delay_s
        self.gate = threading.Event()
        self.chunks = []          # list of record-lists, as received
        self.records = []         # flattened
        self._lsock = socket.socket()
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", port))
        self._lsock.listen(8)
        self.port = self._lsock.getsockname()[1]
        self._stop = threading.Event()
        self._socks = []
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        while not self._stop.is_set():
            try:
                sock, _ = self._lsock.accept()
            except OSError:
                return
            self._socks.append(sock)
            threading.Thread(target=self._conn, args=(sock,),
                             daemon=True).start()

    def _answer(self, rec):
        return wireproto.ResponseRecord(
            rec.req_id, 200, envelope_for(rec.body, self.name))

    def _conn(self, sock):
        dec = wireproto.FrameDecoder()
        try:
            while not self._stop.is_set():
                data = sock.recv(65536)
                if not data:
                    return
                for _kind, records in dec.feed(data):
                    self.chunks.append(records)
                    self.records.extend(records)
                    if self.mode == "hang":
                        continue
                    if self.mode == "gate":
                        self.gate.wait(10)
                    if self.delay_s:
                        time.sleep(self.delay_s)
                    if self.mode == "reverse":
                        for rec in reversed(records):
                            sock.sendall(wireproto.encode_response_chunk(
                                [self._answer(rec)]))
                    else:
                        sock.sendall(wireproto.encode_response_chunk(
                            [self._answer(rec) for rec in records]))
        except OSError:
            return

    def backend(self, replica_id=None, probe_port=0):
        return {"host": "127.0.0.1", "port": self.port,
                "probe_port": probe_port,
                "replica_id": replica_id or self.name}

    def stop(self):
        self._stop.set()
        self.gate.set()
        try:
            # shutdown first: close() alone leaves the accept thread's
            # blocked accept() holding the port in LISTEN
            self._lsock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._lsock.close()
        for s in self._socks:
            try:
                s.close()
            except OSError:
                pass


class ReadyStub:
    """The replica's HTTP listener as the readmission prober sees it:
    every GET answers 200."""

    def __init__(self, port: int = 0):
        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

        self.server = ThreadingHTTPServer(("127.0.0.1", port), H)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


def post(port: int, body: bytes = b"{}", headers=None):
    """One POST on a connection of its own -> (status, headers, body)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("POST", "/v1/admit", body=body,
                     headers=dict({"Content-Type": "application/json"},
                                  **(headers or {})))
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def get(port: int, path: str):
    """One GET -> (status, body)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def raw_post(port, bodies, headers=()):
    """Send len(bodies) pipelined POSTs in ONE write, read all the
    responses off the same connection.  Returns (status, body) pairs in
    arrival order."""
    extra = "".join(f"{k}: {v}\r\n" for k, v in headers)
    wire = b"".join(
        (f"POST /v1/admit HTTP/1.1\r\nHost: d\r\n{extra}"
         f"Content-Length: {len(b)}\r\n\r\n").encode() + b
        for b in bodies
    )
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    s.sendall(wire)
    s.settimeout(10.0)
    buf = b""
    out = []
    while len(out) < len(bodies):
        data = s.recv(65536)
        if not data:
            break
        buf += data
        while True:
            head_end = buf.find(b"\r\n\r\n")
            if head_end < 0:
                break
            head = buf[:head_end].decode("latin-1")
            clen = 0
            for line in head.split("\r\n")[1:]:
                k, _, v = line.partition(":")
                if k.strip().lower() == "content-length":
                    clen = int(v.strip())
            total = head_end + 4 + clen
            if len(buf) < total:
                break
            status = int(head.split(" ", 2)[1])
            out.append((status, buf[head_end + 4:total]))
            buf = buf[total:]
    s.close()
    return out
