"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py sets
XLA_FLAGS for 512 placeholder devices before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic path: arbitrary shapes after fault-tolerance re-planning."""
    return jax.make_mesh(tuple(shape), tuple(axes))
