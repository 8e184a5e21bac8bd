"""Milliseconds inside the dispatch layer's verify_beacons calls per
thousand rounds the window committed (crypto/batch.py; engine_op_seconds
plus a shape's first call in engine_compile_seconds)."""

from harness import counters

OP = "verify_beacons"


def read(ctx):
    before, after = ctx["counters"]
    inside = (counters.delta(before, after, "engine_op_seconds_sum", op=OP)
              + counters.delta(before, after, "engine_compile_seconds_sum",
                               op=OP))
    if inside <= 0 or ctx["work"] <= 0:
        return None
    return inside / ctx["work"] * 1e6
