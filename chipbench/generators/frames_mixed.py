"""`frames` whose callers set behaviours: the same pool of GUBC kind-5 frames
(the same key draws from the same stream, so a seed's frames hold the keys
`frames` gives it), with the per-request bits upstream's clients set
(`proto/gubernator.proto`, `enum Behavior`) OR-ed into the behaviour column of
a share of the lanes.  A gateway tier that aggregates many services' checks
carries whatever bits those services set: a frame is a mix.

`flagged_lane_share` of the lanes, each lane independently, carry exactly one
of `flagged_bits` (NO_BATCHING 1, GLOBAL 2, MULTI_REGION 16), each bit as
likely as another.  The bit is a lane's, not a key's: one key may come flagged
in one lane and plain in the next, in one frame.  None of the three changes an
owner's answer, so the reference holds the cell as it holds `frames`
(`decode` is `frames`'; the load and the read-back are the harness's own
`frames.frame_payload`, behaviour 0)."""

from __future__ import annotations

import numpy as np

from .. import gubc
from . import Request, frames

decode = frames.decode


def frame_payload(pop, idx, behavior, hits: int, host: str) -> bytes:
    """`frames.frame_payload` with a behaviour a lane in place of a key's."""
    n = len(idx)
    name = pop.name.encode()
    body = gubc.encode_frame(
        gubc.fixed_width_column(name * n, n, len(name)),
        gubc.fixed_width_column(pop.keys_blob(idx), n, pop.key_width),
        pop.algo[idx], behavior.astype(np.int32),
        np.full(n, hits, np.int64), pop.limit[idx], pop.duration[idx],
    )
    return gubc.http_request(host, gubc.COLUMNS_CONTENT_TYPE, body)


def lane_behaviors(pop, params: dict, rng, keys: list) -> list:
    """One behaviour column a frame of `keys`, drawn after every key."""
    share = float(params["flagged_lane_share"])
    bits = np.asarray(params["flagged_bits"], np.int32)
    out = []
    for idx in keys:
        flagged = rng.random(len(idx)) < share
        bit = bits[rng.integers(0, len(bits), size=len(idx))]
        out.append(pop.behavior[idx] | np.where(flagged, bit, 0).astype(np.int32))
    return out


def build_pool(pop, params: dict, rng, host: str) -> list:
    lanes = int(params["lanes_per_request"])
    hits = int(params["hits"])
    keys = [pop.draw(rng, lanes) for _ in range(int(params["pool_requests"]))]
    return [
        Request(frame_payload(pop, idx, behavior, hits, host), idx, hits)
        for idx, behavior in zip(keys, lane_behaviors(pop, params, rng, keys))
    ]
