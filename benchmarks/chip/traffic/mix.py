"""What a traffic generator hands the harness."""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as the generator makes it."""
    prompt: np.ndarray             # token ids, int32
    max_new_tokens: int
    temperature: float = 0.0
    shared_prefix: int = 0         # leading tokens computed by an
    #                                earlier request (the algorithm need
    #                                not compute them again)


@dataclasses.dataclass
class Mix:
    """A cell's traffic.

    ``loop="open"``: request ``k`` is due ``due_s[k]`` seconds after the
    window opens, whatever the engine is doing.  ``loop="closed"``:
    ``backlog`` requests are outstanding at all times; request ``k`` is
    sent when the ``k - backlog``-th finishes.  ``setup`` is served
    before the window, as set-up the traffic needs."""
    loop: str
    request: Callable[[int], Req]
    due_s: Optional[np.ndarray] = None
    backlog: int = 0
    setup: List[Req] = dataclasses.field(default_factory=list)
