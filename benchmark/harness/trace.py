"""Device trace: capture one window with the JAX profiler and reduce it.

``capture`` profiles the window (Python tracer off) and ``extract`` keeps
what the reduction needs from the ``.xplane.pb``: per device plane its op
events (line "XLA Ops") and program events (line "XLA Modules"), and the
benchmark's own host spans (``TraceAnnotation`` names that start with
``bench.``). ``reduce`` turns that into busy and idle time, device time per program
(the ops inside each "XLA Modules" event) and per program/op, and the
longest idle gaps named by the innermost benchmark span that covers them.
The extracted form is plain JSON, so the reduction is checked on a
recorded trace in the tests.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import shutil
import tempfile

SPAN_PREFIX = "bench."


@contextlib.contextmanager
def capture(out: dict):
    """Profile the body; on exit ``out["trace"]`` holds ``extract``'s dict
    and the trace files are deleted."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "window"):
            yield
    finally:
        jax.profiler.stop_trace()
        try:
            from jax.profiler import ProfileData

            path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True)[0]
            out["trace"] = extract(ProfileData.from_file(path))
        finally:
            shutil.rmtree(d, ignore_errors=True)


def extract(pd) -> dict:
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[_short(e.name), e.start_ns, e.duration_ns]
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def _short(name: str) -> str:
    """An op event's HLO text cut to its instruction name; a program
    event's name without its run number."""
    import re

    return re.sub(r"\(\d+\)$", "", name.split(" = ", 1)[0].strip())


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy seconds (union of op intervals inside the window, averaged over
    the device planes that ran anything), window seconds, device seconds
    per program and per program/op (summed over devices), and the ``top``
    longest idle gaps inside the window, each named by the innermost
    benchmark span that covers its midpoint."""
    win = [(s, s + d) for n, s, d in tr["spans"]
           if n == SPAN_PREFIX + "window"]
    if not win:
        raise ValueError("the trace holds no window span")
    w0, w1 = win[0]
    used = [d for d in tr["devices"] if d["ops"]]
    if not used:
        raise ValueError("no operation ran on a device in the window")
    busy_ns, per_op, per_module, gaps = 0.0, {}, {}, []
    for dev in used:
        merged = _union((max(s, w0), min(s + d, w1)) for _, s, d in dev["ops"]
                        if s < w1 and s + d > w0)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        mods = sorted((m for m in dev["modules"] if m[1] < w1
                       and m[1] + m[2] > w0), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, d in dev["ops"]:
            if s < w1 and s + d > w0:
                i = bisect.bisect_right(starts, s) - 1
                mod = (mods[i][0] if i >= 0 and s < mods[i][1] + mods[i][2]
                       else "")
                per_op[mod + "/" + name] = per_op.get(mod + "/" + name,
                                                      0.0) + d / 1e9
                per_module[mod] = per_module.get(mod, 0.0) + d / 1e9
    gaps.sort(reverse=True)
    inner = [(n, s, s + d) for n, s, d in tr["spans"]
             if n != SPAN_PREFIX + "window"]

    def cover(mid):
        best = None
        for n, s, e in inner:
            if s <= mid < e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "no benchmark span"

    return {
        "busy_s": busy_ns / len(used) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "per_op_s": per_op,
        "per_program_s": per_module,
        "device_ops": [list(kv) for kv in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[cover((s + e) / 2), g / 1e9]
                      for g, s, e in gaps[:top]],
    }


def family_seconds(red: dict, patterns) -> float | None:
    """Device seconds of the ops inside the programs whose name matches
    any of ``patterns`` (regular expressions); None when none ran."""
    import re

    rx = [re.compile(p) for p in patterns]
    hits = [s for n, s in red["per_program_s"].items()
            if any(r.search(n) for r in rx)]
    return sum(hits) if hits else None
