"""Scoped reset/isolation of the per-process observability singletons.

``FLIGHT``, ``HEALTH`` and ``TRACER`` are deliberately per-process (the
instrumentation sites must not thread a recorder handle through every
layer), which means in-process multi-node harnesses — the e2e suites
and the chaos simulator (ISSUE 11) — all write into the SAME rings and
gauges. Before this module every such test hand-rolled its own subset
of ``.reset()`` calls, and a forgotten one leaked one scenario's rounds,
peer counters or missed-round marker into the next: exactly the kind of
cross-contamination that makes an SLI assertion pass for the wrong
reason.

:func:`reset_observability` is the one authoritative "back to boot
state" — every singleton, every time, so a new singleton added here is
picked up by every harness at once. :func:`isolated_observability`
scopes it: reset on enter AND on exit, so a scenario neither inherits
state nor bequeaths any (the exit half is what hand-rolled resets most
often forgot).

Prometheus counters/gauges are NOT rewound — prometheus state is
cumulative by design and every metric assertion in the tree reads
deltas (conftest.sample_count) — only the recorder/ring state that
snapshot-style assertions read directly, and the per-peer breaker-state
gauge, which the incident time-series sample reads as current state
(a stale OPEN child would read as an open breaker in the next scenario).

Imports are lazy per the ``drand_tpu.obs`` cheapness rule: pulling this
module in costs nothing until a reset actually runs.
"""

from __future__ import annotations

from contextlib import contextmanager


def reset_observability() -> None:
    """Reset FLIGHT (rounds, peers, reachability, DKG timelines),
    HEALTH, TRACER, INCIDENTS (time-series ring + incident state) and
    the remediation ENGINE (ledger, budget, cooldowns, active markers)
    and the breaker-state gauge to boot state. Safe against concurrent
    note_* calls — each singleton's own reset carries its lock
    discipline."""
    from .. import metrics
    from .flight import FLIGHT
    from .health import HEALTH
    from .incident import INCIDENTS
    from .remediate import ENGINE
    from .trace import TRACER

    FLIGHT.reset()
    HEALTH.reset()
    TRACER.reset()
    INCIDENTS.reset()
    ENGINE.reset()
    metrics.PEER_BREAKER_STATE.clear()


@contextmanager
def isolated_observability():
    """Context manager for in-process multi-node harnesses: observability
    singletons are reset on entry (no inherited state) and again on exit
    (nothing leaks into the next scenario/test), even on failure."""
    reset_observability()
    try:
        yield
    finally:
        reset_observability()
