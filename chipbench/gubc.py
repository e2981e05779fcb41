"""The public wire formats, as a client sees them: the GUBC kind-5 request
frame and kind-6 answer frame of `POST /v1/GetRateLimits`, and the classic
JSON call.  Written from the byte layout in architecture.md ("Columnar
pipeline: the front door"); it imports nothing of the program, so a later PR
that changes the program's own encoder cannot change what the benchmark sends.

    request   "GUBC" u8 version=1 u8 kind=5 u32 n
              names:  u32 blob_len, u32 offsets[n+1], blob
              keys:   u32 blob_len, u32 offsets[n+1], blob
              i32 algorithm[n], i32 behavior[n], i64 hits[n], i64 limit[n],
              i64 duration[n]
    answer    "GUBC" u8 version=1 u8 kind=6 u32 n
              i32 status[n], i64 limit[n], i64 remaining[n], i64 reset_time[n]
              u32 n_owner_addrs (0 on one daemon), u32 n_overrides (0 = no
              per-lane error)
"""

from __future__ import annotations

import json
import struct

import numpy as np

MAGIC = b"GUBC"
VERSION = 1
KIND_REQUEST = 5
KIND_ANSWER = 6
COLUMNS_CONTENT_TYPE = "application/x-gubernator-columns"
JSON_CONTENT_TYPE = "application/json"
PATH = "/v1/GetRateLimits"
ALGORITHM_NAMES = ("TOKEN_BUCKET", "LEAKY_BUCKET")
STATUS_NAMES = {"UNDER_LIMIT": 0, "OVER_LIMIT": 1}


class WireError(Exception):
    """An answer that is not the plain answer a sound daemon gives."""


def fixed_width_column(blob: bytes, n: int, width: int) -> bytes:
    """A string column whose `n` strings all have `width` bytes."""
    offsets = np.arange(n + 1, dtype=np.uint32) * np.uint32(width)
    return struct.pack("<I", n * width) + offsets.tobytes() + blob


def encode_frame(names_col: bytes, keys_col: bytes, algorithm, behavior, hits,
                 limit, duration) -> bytes:
    n = len(algorithm)
    return b"".join((
        MAGIC, struct.pack("<BBI", VERSION, KIND_REQUEST, n), names_col, keys_col,
        np.ascontiguousarray(algorithm, np.int32).tobytes(),
        np.ascontiguousarray(behavior, np.int32).tobytes(),
        np.ascontiguousarray(hits, np.int64).tobytes(),
        np.ascontiguousarray(limit, np.int64).tobytes(),
        np.ascontiguousarray(duration, np.int64).tobytes(),
    ))


def decode_answer_frame(raw: bytes, n_sent: int):
    """(status i32[n], limit i64[n], remaining i64[n], reset_time i64[n]) of a
    plain kind-6 answer.  Anything else (another kind, a lane count that is not
    the request's, owner columns, a per-lane error) raises WireError."""
    if len(raw) < 10 or raw[:4] != MAGIC:
        raise WireError(f"not a GUBC frame: {raw[:60]!r}")
    version, kind, n = struct.unpack_from("<BBI", raw, 4)
    if version != VERSION or kind != KIND_ANSWER or n != n_sent:
        raise WireError(f"version {version} kind {kind} lanes {n}, sent {n_sent}")
    if len(raw) != 10 + 28 * n + 8:
        raise WireError(f"answer of {len(raw)} bytes for {n} lanes: owners or per-lane errors")
    status = np.frombuffer(raw, np.int32, n, 10)
    limit = np.frombuffer(raw, np.int64, n, 10 + 4 * n)
    remaining = np.frombuffer(raw, np.int64, n, 10 + 12 * n)
    reset_time = np.frombuffer(raw, np.int64, n, 10 + 20 * n)
    tail = struct.unpack_from("<II", raw, 10 + 28 * n)
    if tail != (0, 0):
        raise WireError(f"answer carries owners/overrides {tail}")
    return status, limit, remaining, reset_time


def encode_json_call(checks) -> bytes:
    """`checks`: (name, unique_key, algorithm, hits, limit, duration, behavior)
    tuples; `behavior` is the proto's bit set as a number (0, or 4 with
    `duration` a calendar interval number)."""
    return json.dumps({"requests": [
        {"name": name, "uniqueKey": key, "hits": str(hits), "limit": str(limit),
         "duration": str(duration), "algorithm": ALGORITHM_NAMES[algo], "behavior": behavior}
        for name, key, algo, hits, limit, duration, behavior in checks
    ]}, separators=(",", ":")).encode()


def decode_json_answer(raw: bytes, n_sent: int):
    try:
        responses = json.loads(raw)["responses"]
    except (ValueError, KeyError, TypeError) as e:
        raise WireError(f"not a GetRateLimits answer: {raw[:120]!r}") from e
    if len(responses) != n_sent:
        raise WireError(f"{len(responses)} answers for {n_sent} checks")
    status = np.empty(n_sent, np.int32)
    limit = np.empty(n_sent, np.int64)
    remaining = np.empty(n_sent, np.int64)
    reset_time = np.empty(n_sent, np.int64)
    for i, r in enumerate(responses):
        if r.get("error"):
            raise WireError(f"check answered error {r['error']!r}")
        status[i] = STATUS_NAMES[r.get("status", "UNDER_LIMIT")]
        limit[i] = int(r.get("limit", 0))
        remaining[i] = int(r.get("remaining", 0))
        reset_time[i] = int(r.get("resetTime", 0))
    return status, limit, remaining, reset_time


def http_request(host: str, content_type: str, body: bytes) -> bytes:
    return (
        f"POST {PATH} HTTP/1.1\r\nHost: {host}\r\nContent-Type: {content_type}\r\n"
        f"Content-Length: {len(body)}\r\n\r\n"
    ).encode() + body


def parse_http_head(buf) -> "tuple[int, int, int] | None":
    """(status, where the body starts, where the answer ends) of the HTTP
    answer at the front of `buf`, or None while its head is incomplete."""
    end = buf.find(b"\r\n\r\n")
    if end < 0:
        return None
    head = bytes(buf[:end]).split(b"\r\n")
    length = 0
    for line in head[1:]:
        k, _, v = line.partition(b":")
        if k.strip().lower() == b"content-length":
            length = int(v)
    return int(head[0].split()[1]), end + 4, end + 4 + length
