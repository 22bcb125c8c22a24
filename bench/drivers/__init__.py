"""One module per kind of traffic; a traffic file's ``driver`` key names
it. A driver's ``Driver(ctx)`` has ``setup()``, ``window(seconds)`` (the
timed loop, returning its counters with ``attempted`` and ``failed``),
``e2e(counters)``, ``scope_maps()``, ``release()`` and ``check(counters)``
(a list of :func:`check` entries)."""
from __future__ import annotations

import numpy as np


def sample(seed: int, kept: dict, count: int) -> list[int]:
    """``count`` of the kept calls, drawn from the seed."""
    rng = np.random.default_rng([seed, 3])
    return sorted(rng.choice(sorted(kept), min(count, len(kept)),
                             replace=False).tolist())


def check(name: str, value: float, limit: float) -> dict:
    """One number compared with its limit (NaN, nothing compared, fails)."""
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(value <= limit)}
