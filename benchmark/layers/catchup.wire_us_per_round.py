"""Device microseconds per committed round in the wire programs: signature
decode, hash-to-G2 and the lane MSM (ops/pallas_wire.py _sig_pl,
_hash_msgs_pl; ops/pallas_msm.py _msm_call), from the trace's program
names."""

from harness import trace

PATTERNS = [r"_sig_pl", r"_hash_msgs_pl", r"_msm_call"]


def read(ctx):
    red = ctx["trace"]
    s = trace.family_seconds(red, PATTERNS) if red else None
    if s is None or ctx["work"] <= 0:
        return None
    return s / ctx["work"] * 1e6
