"""The distributed layer's ``shard_map`` import, in one place.

Every distributed module imports it from here, so a future move of the
API touches one line.
"""
from __future__ import annotations

from jax import shard_map

__all__ = ["shard_map"]
