"""Share of the catch-up window not spent inside the dispatch layer's
verify_beacons calls (engine_op_seconds and, for a shape's first call,
engine_compile_seconds, op="verify_beacons"): fetch, the linkage scan,
the head check and thread hand-offs in client/verify.py."""

from harness import counters

OP = "verify_beacons"


def read(ctx):
    before, after = ctx["counters"]
    inside = (counters.delta(before, after, "engine_op_seconds_sum", op=OP)
              + counters.delta(before, after, "engine_compile_seconds_sum",
                               op=OP))
    if inside <= 0:
        return None
    return 100.0 * (1.0 - inside / ctx["window_s"])
