"""GUBC kind-5 frames of `lanes_per_request` checks: what a gateway tier that
aggregates its callers' checks sends."""

from __future__ import annotations

import numpy as np

from .. import gubc
from . import Request

decode = gubc.decode_answer_frame


def frame_payload(pop, idx, hits, host: str) -> bytes:
    """One frame over the keys `idx` (`hits` a scalar or one value a lane)."""
    n = len(idx)
    name = pop.name.encode()
    body = gubc.encode_frame(
        gubc.fixed_width_column(name * n, n, len(name)),
        gubc.fixed_width_column(pop.keys_blob(idx), n, pop.key_width),
        pop.algo[idx], pop.behavior[idx],
        np.broadcast_to(np.asarray(hits, np.int64), (n,)), pop.limit[idx],
        pop.duration[idx],
    )
    return gubc.http_request(host, gubc.COLUMNS_CONTENT_TYPE, body)


def build_pool(pop, params: dict, rng, host: str) -> list:
    lanes = int(params["lanes_per_request"])
    hits = int(params["hits"])
    pool = []
    for _ in range(int(params["pool_requests"])):
        idx = pop.draw(rng, lanes)
        pool.append(Request(frame_payload(pop, idx, hits, host), idx, hits))
    return pool
