"""One module per traffic `kind`: it turns a traffic file's parameters into a
pool of pre-encoded requests and decodes the answers.  `run.py` finds the
module by the `kind` the traffic file names."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    payload: bytes  # the whole HTTP request, ready for send()
    keys: np.ndarray  # int32 indices into the population, one per check
    hits: int  # every check of a request takes this many hits
