"""Bytes a lookup has to move, from the configuration and the build's
declared error bound alone (never from tile or block sizes).

A lookup reads its 8-byte query and writes an 8-byte answer.  The last
mile reads the key window the index guarantees holds the answer:
``max_err`` keys of 8 bytes.  The predict path reads one set of model
parameters per level the build reports (``levels``): a PGM segment's
anchor, intercept and slope; a RadixSpline's two radix-table entries and
its two spline knots.  The configuration states that size
(``model_bytes_per_level``).  Everything shared by a whole batch (a
PGM's top level) is left out, so these are lower bounds: the roofline
shares computed from them cannot pass 100% by overcounting.
"""
KEY_BYTES = 8
ANSWER_BYTES = 8


def search_bytes(build: dict) -> int:
    """Bytes of one lookup's last mile: query, guaranteed window, answer."""
    return KEY_BYTES + KEY_BYTES * int(build["max_err"]) + ANSWER_BYTES


def lookup_bytes(config: dict, build: dict) -> int:
    """Bytes of one whole lookup: the last mile plus the model path."""
    return (search_bytes(build)
            + int(config["model_bytes_per_level"]) * int(build["levels"]))
