"""The system under test as a child process, and what it says about itself.

The parent never initialises a JAX backend; the daemon child holds the chip.
(The shape of `chip_smoke.py`'s `DaemonProc`, PR 23, copied so that a later PR
may change that script.)"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

from .gubc import parse_http_head

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(REPO, "chipbench", "out")
SERVER_ARGV = [sys.executable, "-m", "gubernator_tpu.cmd.server"]
STOP_LIMIT_S = 60.0


class BenchFailure(Exception):
    """The run cannot give a result; it ends non-zero with no result line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class DaemonProc:
    """`python -m gubernator_tpu.cmd.server` (or, traced, the same entry called
    in-process by `traced_daemon.py`) with the caller's environment plus the
    configuration's GUBER_* settings.  Nothing here names a JAX platform."""

    def __init__(self, label: str, env_extra: dict, argv=None):
        self.http = f"127.0.0.1:{free_port()}"
        env = dict(os.environ)
        env.update(env_extra)
        env.update(GUBER_HTTP_ADDRESS=self.http, GUBER_GRPC_ADDRESS=f"127.0.0.1:{free_port()}")
        env.setdefault("JAX_LOG_COMPILES", "1")  # each program's seconds, into the stderr file
        os.makedirs(OUT_DIR, exist_ok=True)
        self.stderr_path = os.path.join(OUT_DIR, f"{label}.daemon.stderr")
        self._stderr = open(self.stderr_path, "wb")
        self.t_spawn = time.perf_counter()
        self.proc = subprocess.Popen(
            argv or SERVER_ARGV, stdout=subprocess.PIPE, stderr=self._stderr, env=env,
            cwd=REPO, text=True,
        )
        self.listening_s = float("nan")

    def stderr_tail(self, n: int = 3000) -> str:
        self._stderr.flush()
        with open(self.stderr_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.stderr_path) - n))
            return f.read().decode("utf-8", "replace")

    def wait_listening(self, limit_s: float) -> None:
        line: list = []
        reader = threading.Thread(
            target=lambda: line.append(self.proc.stdout.readline()), daemon=True
        )
        reader.start()
        reader.join(limit_s)
        self.listening_s = time.perf_counter() - self.t_spawn
        if not line or "listening" not in line[0]:
            state = (f"exited with status {self.proc.poll()}" if self.proc.poll() is not None
                     else "still starting")
            raise BenchFailure(
                f"no 'listening' line after {self.listening_s:.1f} s (daemon {state}); "
                f"stderr tail:\n{self.stderr_tail()}"
            )

    def signal(self, sig: int) -> None:
        self.proc.send_signal(sig)

    def stop(self) -> None:
        """SIGTERM, and insist on exit status 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(STOP_LIMIT_S)
        except subprocess.TimeoutExpired:
            raise BenchFailure(
                f"daemon still running {STOP_LIMIT_S:.0f} s after SIGTERM; stderr tail:\n"
                f"{self.stderr_tail()}"
            ) from None
        if rc != 0:
            raise BenchFailure(f"daemon exit status {rc} after SIGTERM; stderr tail:\n{self.stderr_tail()}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.proc.stdout.close()
        self._stderr.close()


class Http:
    """One blocking keep-alive connection for the untimed requests: the load,
    the read-back and the daemon's debug documents."""

    def __init__(self, address: str, timeout_s: float = 300.0):
        host, port = address.rsplit(":", 1)
        self.host = address
        self.sock = socket.create_connection((host, int(port)), timeout=timeout_s)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()

    def close(self) -> None:
        self.sock.close()

    def roundtrip(self, payload: bytes) -> bytes:
        """Send one whole HTTP request; return the body of its 200 answer."""
        self.sock.sendall(payload)
        while (head := parse_http_head(self.buf)) is None:
            self._more()
        status, body_at, need = head
        while len(self.buf) < need:
            self._more()
        body = bytes(self.buf[body_at:need])
        del self.buf[:need]
        if status != 200:
            raise BenchFailure(f"HTTP {status} for {payload[:40]!r}: {body[:300]!r}")
        return body

    def _more(self) -> None:
        chunk = self.sock.recv(1 << 20)
        if not chunk:
            raise BenchFailure("the daemon closed the connection")
        self.buf += chunk

    def get(self, path: str) -> bytes:
        return self.roundtrip(f"GET {path} HTTP/1.1\r\nHost: {self.host}\r\n\r\n".encode())

    def get_json(self, path: str) -> dict:
        return json.loads(self.get(path))

    def scrape(self) -> list:
        """`/metrics` as (name, labels-text, value) rows."""
        rows = []
        for line in self.get("/metrics").decode().splitlines():
            m = _SAMPLE.match(line)
            if m:  # comment lines start with '#', which no metric name does
                rows.append((m.group(1), m.group(2) or "", float(m.group(3))))
        return rows


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def metric_sum(rows: list, name: str, label_has: str = "") -> float:
    return sum(v for n, labels, v in rows if n == name and label_has in labels)
