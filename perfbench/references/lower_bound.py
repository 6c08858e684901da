"""The plain reference of every SOSD configuration: the lower-bound rank,
the smallest i with ``keys[i] >= q``, by `np.searchsorted` on the host
copy of the key set.  Independent of the code under test."""
import numpy as np


def answers(keys: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """int64 lower-bound ranks of ``queries`` (searched in sorted order,
    which keeps the binary searches' paths in cache)."""
    queries = np.asarray(queries, dtype=np.uint64)
    order = np.argsort(queries, kind="stable")
    out = np.empty(queries.size, np.int64)
    out[order] = np.searchsorted(keys, queries[order], side="left")
    return out
