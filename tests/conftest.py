"""Shared pytest configuration.

Registers and loads one Hypothesis profile, whatever the environment:
derandomized, so every run (local, CI, tier-1) draws the same examples and
the example database is off, and with no deadline, since JAX compile times
would trip it. Each property sets its own ``max_examples``.
"""
from __future__ import annotations

try:
    from hypothesis import settings
except ImportError:  # hypothesis is an optional test dep (importorskip)
    pass
else:
    settings.register_profile("repro", derandomize=True, deadline=None,
                              print_blob=True)
    settings.load_profile("repro")
