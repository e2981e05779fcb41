"""Classic JSON `POST /v1/GetRateLimits` calls of `checks_per_request`
checks: what a stock Gubernator client sends."""

from __future__ import annotations

from .. import gubc
from . import Request

decode = gubc.decode_json_answer


def build_pool(pop, params: dict, rng, host: str) -> list:
    per = int(params["checks_per_request"])
    hits = int(params["hits"])
    count = int(params["pool_requests"])
    idx = pop.draw(rng, per * count).reshape(count, per)
    pool = []
    for row in idx:
        body = gubc.encode_json_call([
            (pop.name, pop.unique_key(i), int(pop.algo[i]), hits, int(pop.limit[i]),
             int(pop.duration[i]), int(pop.behavior[i]))
            for i in row.tolist()
        ])
        pool.append(Request(gubc.http_request(host, gubc.JSON_CONTENT_TYPE, body), row, hits))
    return pool
