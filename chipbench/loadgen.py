"""The load generator: one thread, one selector, `connections` keep-alive
sockets, each with at most one request in flight.  It sends pre-encoded
requests and keeps the raw answers; nothing is decoded here.

Closed loop: a connection sends its next request as soon as its answer is in.
Open loop: requests fall due on a schedule made from the seed (`rate_per_s`,
exponential gaps), go out on the first idle connection, and are timed from when
they were DUE, so a stall is charged to every request it delayed.
"""

from __future__ import annotations

import selectors
import socket
import time
from dataclasses import dataclass, field

import numpy as np

from .gubc import parse_http_head


class LoadError(Exception):
    """The transport failed under the generator (not a wrong answer)."""


@dataclass
class Done:
    pool_index: int
    t_start: float  # perf_counter: when sent (closed loop) or when due (open loop)
    t_done: float  # perf_counter: last byte of the answer read
    wall_send_ms: int  # wall clock, floor: the daemon stamped the request after this
    wall_recv_ms: int  # wall clock, ceiling: and before this
    status: int  # HTTP status
    body: bytes


@dataclass
class _Conn:
    index: int
    sock: socket.socket
    order: np.ndarray  # pool indices this connection walks, cyclically
    cursor: int = 0
    out: memoryview | None = None  # unsent rest of the request in flight
    buf: bytearray = field(default_factory=bytearray)
    need: int = -1  # total bytes of the answer, once the header is read
    body_at: int = 0
    http_status: int = 0
    pool_index: int = -1
    t_start: float = 0.0
    wall_send_ms: int = 0
    busy: bool = False
    t_last_done: float = 0.0
    between_s: float = 0.0  # summed: answer read -> next request written


def wall_floor_ms() -> int:
    return time.time_ns() // 1_000_000


def wall_ceil_ms() -> int:
    return -(-time.time_ns() // 1_000_000)


class LoadGen:
    def __init__(self, address: str, pool: list, params: dict, seed: int):
        host, port = address.rsplit(":", 1)
        self.pool = pool
        self.open_loop = params["loop"] == "open"
        self.rate = float(params.get("rate_per_s", 0.0))
        if self.open_loop and self.rate <= 0:
            raise ValueError("an open loop needs rate_per_s > 0")
        self.rng = np.random.default_rng([seed, 0x6C6F6164])
        n = int(params["connections"])
        self.sel = selectors.DefaultSelector()
        self.conns = []
        for i in range(n):
            s = socket.create_connection((host, int(port)), timeout=30.0)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.setblocking(False)
            # Every connection walks the whole pool, in an order of its own.
            c = _Conn(i, s, self.rng.permutation(len(pool)))
            self.conns.append(c)
            self.sel.register(s, selectors.EVENT_READ, c)
        self.done: list = []
        self.late_s: list = []  # open loop: sent this long after due

    def close(self) -> None:
        for c in self.conns:
            try:
                self.sel.unregister(c.sock)
            except (KeyError, ValueError):
                pass
            c.sock.close()
        self.sel.close()

    # -- one connection ---------------------------------------------------
    def _send(self, c: _Conn, t_start: float) -> None:
        c.pool_index = int(c.order[c.cursor % len(c.order)])
        c.cursor += 1
        c.out = memoryview(self.pool[c.pool_index].payload)
        c.busy = True
        c.t_start = t_start
        c.wall_send_ms = wall_floor_ms()
        self._flush(c)

    def _flush(self, c: _Conn) -> None:
        while c.out is not None:
            try:
                sent = c.sock.send(c.out)
            except BlockingIOError:
                self.sel.modify(c.sock, selectors.EVENT_READ | selectors.EVENT_WRITE, c)
                return
            c.out = c.out[sent:] if sent < len(c.out) else None
        if c.t_last_done:
            c.between_s += time.perf_counter() - c.t_last_done
            c.t_last_done = 0.0
        self.sel.modify(c.sock, selectors.EVENT_READ, c)

    def _read(self, c: _Conn) -> bool:
        """True when the answer in flight is complete (and recorded)."""
        try:
            chunk = c.sock.recv(1 << 20)
        except BlockingIOError:
            return False
        if not chunk:
            raise LoadError(f"connection {c.index} closed by the daemon")
        c.buf += chunk
        if c.need < 0:
            head = parse_http_head(c.buf)
            if head is None:
                return False
            c.http_status, c.body_at, c.need = head
        if len(c.buf) < c.need:
            return False
        now = time.perf_counter()
        body = bytes(c.buf[c.body_at:c.need])
        self.done.append(Done(
            c.pool_index, c.t_start, now, c.wall_send_ms, wall_ceil_ms(),
            c.http_status, body,
        ))
        del c.buf[:c.need]
        c.need = -1
        c.busy = False
        c.t_last_done = now
        return True

    # -- the run ----------------------------------------------------------
    def run(self, ramp_s: float, window_s: float, at_offsets=()) -> "tuple[float, float]":
        """Ramp, then the window; returns (t0, t1) of the window on the
        perf_counter clock.  Answers to requests sent before t1 are still
        read after it.  `at_offsets` are (seconds into the window, callable)
        pairs, each called once from this thread."""
        t_begin = time.perf_counter()
        t0 = t_begin + ramp_s
        t1 = t0 + window_s
        hooks = sorted(((t0 + off, fn) for off, fn in at_offsets), key=lambda h: h[0])
        next_due = t_begin
        if not self.open_loop:
            for c in self.conns:
                self._send(c, time.perf_counter())
        while True:
            now = time.perf_counter()
            while hooks and hooks[0][0] <= now:
                hooks.pop(0)[1]()
            sending = now < t1
            if self.open_loop and sending:
                while next_due <= now:
                    idle = next((c for c in self.conns if not c.busy), None)
                    if idle is None:
                        break  # every connection is busy: the request waits, its clock runs
                    self.late_s.append(now - next_due)
                    self._send(idle, next_due)
                    next_due += self.rng.exponential(1.0 / self.rate)
            if not sending and not any(c.busy for c in self.conns):
                return t0, t1
            timeout = 0.05
            if self.open_loop and sending:
                timeout = min(timeout, max(0.0, next_due - now))
            for key, mask in self.sel.select(timeout):
                c = key.data
                if mask & selectors.EVENT_WRITE:
                    self._flush(c)
                if mask & selectors.EVENT_READ and self._read(c):
                    if not self.open_loop and time.perf_counter() < t1:
                        self._send(c, time.perf_counter())
            if time.perf_counter() > t1 + 120.0:
                raise LoadError("answers still outstanding 120 s after the window closed")
