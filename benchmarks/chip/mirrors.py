"""The benchmark's own HTTP range mirrors, one child process each.

A mirror serves files it maps from inherited file descriptors (the
checkpoint's ``manifest.json`` and ``data.bin``, held in memory files) over
HTTP/1.1 keep-alive: ``Range``/``Content-Range``, ``HEAD``, and an
``X-Range-Checksum: crc32:<hex>`` header on every body.  That is the
identity-range wire format of ``repro.transfer.RangeServer``, written again
on the standard library alone, so that nothing of the program under test
paces, delays or counts the traffic.

The network each mirror stands for:

* One egress clock shared by all of the mirror's connections, kept as a
  token bucket one bandwidth-delay product deep (rate x RTT, at least one
  piece).  A piece of a body is written when the link would have finished
  sending it.  A write the host makes late is caught up, as far as the
  bucket holds; link time lost beyond it, as while the client leaves its
  socket full, stays lost.  The bytes written over any interval never
  exceed rate x its length plus the bucket.
* A response starts no earlier than its request's arrival plus the RTT.
  Requests are stamped as they come off the socket, so a client that
  pipelines overlaps their RTTs, as on a real path.
* Rate 0 is unthrottled: bodies go out with ``os.sendfile``.

The parent drives a mirror through its stdin and stdout, one JSON object a
line (:class:`Mirror`).  The child runs as ``python -I`` with
``JAX_PLATFORMS=cpu``, imports only the standard library, and reports any
``jax`` or ``repro`` module that it finds loaded.
"""

from __future__ import annotations

import json
import mmap
import os
import queue
import socket
import subprocess
import sys
import threading
import time
import zlib
from dataclasses import dataclass

#: bytes per paced write: 0.9 ms of wire at 70 MiB/s, 5.2 ms at 12 MiB/s
PIECE = 64 * 1024
_FORBIDDEN = ("jax", "jaxlib", "repro")


@dataclass
class _Response:
    ready: float        # monotonic instant the response may start
    head: bytes
    fd: int = -1
    offset: int = 0
    length: int = 0
    close: bool = False


class _Server:
    """One mirror: a listener, a reader and a writer thread per connection,
    and the egress clock and counters they share."""

    def __init__(self, files: dict[str, int], rate: float, rtt: float):
        self.files = {}
        self._maps = []
        for path, fd in files.items():
            size = os.fstat(fd).st_size
            view = b""
            if size:
                m = mmap.mmap(fd, size, prot=mmap.PROT_READ)
                self._maps.append(m)
                view = memoryview(m)
            self.files[path] = (fd, view, size)
        self.rate = float(rate)
        self.rtt = float(rtt)
        #: the egress clock may run this many seconds behind the present
        self.burst_s = max(PIECE / self.rate, self.rtt) if self.rate else 0.0
        self.lock = threading.Lock()
        self.free_at = 0.0          # the egress clock
        self.served = 0             # body bytes handed to the kernel
        self.last_byte = 0.0        # monotonic time of the last body byte
        self.requests = 0
        self.alive: list[list] = []                 # [start, stop or None]
        self.conns: set = set()
        self.listener = None
        self.port = 0

    # -- control -----------------------------------------------------------

    def start(self) -> None:
        if self.listener is not None:
            return
        ls = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        ls.bind(("127.0.0.1", self.port))
        ls.listen(128)
        self.port = ls.getsockname()[1]
        with self.lock:
            self.listener = ls
            self.alive.append([time.monotonic(), None])
        threading.Thread(target=self._accept, args=(ls,), daemon=True).start()

    def stop(self) -> None:
        """Close the listener and sever every open connection."""
        with self.lock:
            ls, self.listener = self.listener, None
            if ls is None:
                return
            self.alive[-1][1] = time.monotonic()
            conns = list(self.conns)
        for s in [ls, *conns]:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        ls.close()

    def stats(self) -> dict:
        with self.lock:
            return {"served": self.served, "last_byte": self.last_byte,
                    "requests": self.requests,
                    "alive": [list(a) for a in self.alive],
                    "imported": sorted(
                        m for m in sys.modules
                        if m.split(".")[0] in _FORBIDDEN)}

    # -- connections -------------------------------------------------------

    def _accept(self, ls) -> None:
        while True:
            try:
                conn, _ = ls.accept()
            except OSError:
                return
            with self.lock:
                if self.listener is not ls:
                    conn.close()
                    return
                self.conns.add(conn)
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn) -> None:
        """Read requests, stamping each as it arrives; a writer thread
        answers them in order.  This thread alone closes the socket."""
        q: queue.SimpleQueue = queue.SimpleQueue()
        writer = threading.Thread(target=self._write, args=(conn, q),
                                  daemon=True)
        writer.start()
        buf = b""
        try:
            while True:
                data = conn.recv(65536)
                arrived = time.monotonic()
                if not data:
                    break
                buf += data
                while (end := buf.find(b"\r\n\r\n")) >= 0:
                    head, buf = buf[:end], buf[end + 4:]
                    q.put(self._respond(head.decode("latin-1"), arrived))
        except OSError:
            pass
        finally:
            q.put(None)
            writer.join()
            with self.lock:
                self.conns.discard(conn)
            conn.close()

    def _respond(self, head: str, arrived: float) -> _Response:
        ready = arrived + self.rtt
        lines = head.split("\r\n")
        parts = lines[0].split(" ")
        if len(parts) != 3 or parts[0] not in ("GET", "HEAD"):
            return _Response(ready, _head(400, {"Content-Length": "0"}),
                             close=True)
        method, path, _ = parts
        headers = {}
        for line in lines[1:]:
            k, _, v = line.partition(":")
            headers[k.strip().lower()] = v.strip()
        close = headers.get("connection", "").lower() == "close"
        entry = self.files.get(path)
        if entry is None:
            return _Response(ready, _head(404, {"Content-Length": "0"}),
                             close=close)
        fd, view, total = entry
        if method == "HEAD":
            return _Response(ready, _head(200, {
                "Content-Length": str(total), "Accept-Ranges": "bytes"}),
                close=close)
        with self.lock:
            self.requests += 1
        lo, hi, status = 0, total - 1, 200
        rng = headers.get("range", "")
        if rng.startswith("bytes="):
            try:
                lo_s, hi_s = rng[len("bytes="):].split("-", 1)
                lo = int(lo_s)
                hi = min(int(hi_s) if hi_s else total - 1, total - 1)
            except ValueError:
                lo, hi = 1, 0
            if not 0 <= lo <= hi:
                return _Response(ready, _head(416, {
                    "Content-Length": "0",
                    "Content-Range": f"bytes */{total}"}), close=close)
            status = 206
        fields = {"Content-Length": str(hi - lo + 1), "Accept-Ranges": "bytes",
                  "X-Range-Checksum":
                      f"crc32:{zlib.crc32(view[lo:hi + 1]):08x}"}
        if status == 206:
            fields["Content-Range"] = f"bytes {lo}-{hi}/{total}"
        return _Response(ready, _head(status, fields), fd, lo, hi - lo + 1,
                         close)

    def _write(self, conn, q: queue.SimpleQueue) -> None:
        try:
            while (resp := q.get()) is not None:
                wait = resp.ready - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                conn.sendall(resp.head)
                if resp.length:
                    self._body(conn, resp)
                if resp.close:
                    break
        except OSError:
            pass
        # wake the reader (a severed or finished connection)
        try:
            conn.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

    def _body(self, conn, resp: _Response) -> None:
        sent = 0
        while sent < resp.length:
            n = resp.length - sent
            if self.rate > 0:
                n = min(n, PIECE)
                with self.lock:
                    due = max(self.free_at, resp.ready,
                              time.monotonic() - self.burst_s) + n / self.rate
                    self.free_at = due
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
            end = sent + n
            while sent < end:
                k = os.sendfile(conn.fileno(), resp.fd, resp.offset + sent,
                                end - sent)
                if k <= 0:
                    raise OSError("sendfile wrote nothing")
                sent += k
                with self.lock:
                    self.served += k
                    self.last_byte = time.monotonic()


_REASONS = {200: "OK", 206: "Partial Content", 400: "Bad Request",
            404: "Not Found", 416: "Range Not Satisfiable"}


def _head(status: int, fields: dict) -> bytes:
    lines = [f"HTTP/1.1 {status} {_REASONS[status]}"]
    lines += [f"{k}: {v}" for k, v in fields.items()]
    return ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1")


def _child_main(spec: str) -> None:
    cfg = json.loads(spec)
    srv = _Server(cfg["files"], cfg["rate"], cfg["rtt"])
    srv.start()
    out = sys.stdout
    out.write(json.dumps({"port": srv.port}) + "\n")
    out.flush()
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd.pop("cmd")
        if op == "exit":
            srv.stop()
            break
        if op == "stop":
            srv.stop()
        elif op == "start":
            srv.start()
        reply = srv.stats() if op == "stats" else {"ok": op}
        out.write(json.dumps(reply) + "\n")
        out.flush()


class Mirror:
    """The parent's handle on one mirror process.

    ``files`` maps a URL path to a file descriptor that the child inherits
    and maps read-only; ``rate`` is bytes/s (0: unthrottled) and ``rtt``
    seconds.  The process starts at once; ``port`` waits until it listens.
    Every call blocks until the child has acted on it."""

    def __init__(self, files: dict[str, int], rate: float, rtt: float):
        spec = json.dumps({"files": files, "rate": rate, "rtt": rtt})
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.rate, self.rtt = float(rate), float(rtt)
        self._lock = threading.Lock()
        self._proc = subprocess.Popen(
            [sys.executable, "-I", os.path.abspath(__file__), spec],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            pass_fds=tuple(files.values()), env=env)
        self._port = None

    @property
    def port(self) -> int:
        with self._lock:
            if self._port is None:
                self._port = self._reply()["port"]
            return self._port

    def _reply(self) -> dict:
        line = self._proc.stdout.readline()
        if not line:
            raise RuntimeError(f"mirror process ended "
                               f"(exit code {self._proc.poll()})")
        return json.loads(line)

    def _call(self, **cmd) -> dict:
        self.port                       # the child's first line
        with self._lock:
            self._proc.stdin.write(json.dumps(cmd) + "\n")
            self._proc.stdin.flush()
            return self._reply()

    def stop(self) -> None:
        self._call(cmd="stop")

    def start(self) -> None:
        self._call(cmd="start")

    def stats(self) -> dict:
        """``served`` body bytes, ``last_byte`` (CLOCK_MONOTONIC),
        ``requests``, ``alive`` intervals, and ``imported``: any jax or repro module loaded in the child."""
        return self._call(cmd="stats")

    def close(self) -> None:
        """End the process and wait for it (idempotent)."""
        if self._proc.poll() is None:
            try:
                with self._lock:
                    self._proc.stdin.write('{"cmd": "exit"}\n')
                    self._proc.stdin.flush()
                self._proc.wait(timeout=10)
            except (OSError, ValueError, subprocess.TimeoutExpired):
                self._proc.kill()
                self._proc.wait()
        for f in (self._proc.stdin, self._proc.stdout):
            if f is not None:
                f.close()


if __name__ == "__main__":
    _child_main(sys.argv[1])
