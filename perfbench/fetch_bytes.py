"""Bytes the RMI's stage-2 fetch has to move, from the configuration
alone (never from tile or block sizes).

A lookup reads the one stage-2 model its root selects: the row's slope
and intercept in float32 and its error bound in int32, 12 bytes, which
the configuration states (``model_bytes_per_level``).  The root is
shared by a whole batch and left out, as are the bucket the kernel is
handed and the row it writes back, so this is a lower bound: the
roofline share computed from it cannot pass 100% by overcounting.
"""


def fetch_bytes(config: dict) -> int:
    """Bytes of one lookup's stage-2 fetch: one model row."""
    return int(config["model_bytes_per_level"])
