"""Mesh construction.

A FUNCTION (never a module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def make_local_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    n = jax.device_count()
    return jax.make_mesh((1, n), ("data", "model"))
