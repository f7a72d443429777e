"""The traffic mix and the arithmetic the end-to-end rate is made of.

A traffic mix is a data file (``bench/traffic/<name>.json``) read by one
general generator in ``serve.py``:

* ``{"kind": "backlog", "depth": D}`` -- closed loop: before every poll the
  generator tops the admission queue up to ``D`` pending requests.
"""
from __future__ import annotations

KINDS = ("backlog",)


def check_mix(mix: dict) -> dict:
    kind = mix.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind must be one of {KINDS}, got {kind!r}")
    if int(mix["depth"]) < 1:
        raise ValueError("backlog depth must be >= 1")
    return mix


def rate(count: int, window_s: float) -> float:
    if window_s <= 0:
        raise ValueError("window must be positive")
    return count / window_s
