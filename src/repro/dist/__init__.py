"""Distributed execution layer: sharding rules, pipeline schedule, comms.

Three orthogonal pieces (DESIGN.md §8):

  sharding           logical-axis-name -> PartitionSpec resolution over the
                     launch/mesh.py mesh (GSPMD; the model code only names
                     axes, never touches device topology)
  pipeline_parallel  microbatched GPipe schedule over the `model` mesh axis
                     with exact parity against the sequential stack
  compression        int8 gradient all-reduce with error feedback

`shard_map` is re-exported here as `jax.shard_map`.
"""
from __future__ import annotations

from jax import shard_map  # noqa: F401

from repro.dist import compression, pipeline_parallel, sharding  # noqa: F401,E402

__all__ = ["compression", "pipeline_parallel", "sharding", "shard_map"]
