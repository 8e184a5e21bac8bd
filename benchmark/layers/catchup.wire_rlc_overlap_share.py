"""Share of the wire-RLC combine's slices whose host preparation was
wholly hidden: the span's previous slice was still running on the
device once this one was launched (ops/engine.py verify_wire_rlc;
engine_wire_rlc_slices_total{prep}). The first slice of every span is
exposed. None where the program has no such counter."""

from harness import counters

NAME = "engine_wire_rlc_slices_total"


def read(ctx):
    before, after = ctx["counters"]
    overlapped = counters.delta(before, after, NAME, prep="overlapped")
    exposed = counters.delta(before, after, NAME, prep="exposed")
    if overlapped + exposed <= 0:
        return None
    return 100.0 * overlapped / (overlapped + exposed)
