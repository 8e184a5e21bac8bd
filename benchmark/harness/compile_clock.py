"""Seconds JAX spends tracing, lowering and compiling, from its monitoring
events: the union of trace spans (nested jits overlap) plus every
lowering and backend compile, with persistent-cache hits and misses."""

from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax.monitoring as mon

        self.trace_spans = []
        self.lower = self.compile = self.saved = 0.0
        self.per_program: dict[str, float] = {}
        self.cache = {"cache_hits": 0, "cache_misses": 0}

        def span(event, start, end, **_):
            if event == "/jax/core/compile/jaxpr_trace_duration":
                self.trace_spans.append((start, end))

        def duration(event, secs, fun_name="?", **_):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lower += secs
            elif event == "/jax/core/compile/backend_compile_duration":
                self.compile += secs
            elif event == "/jax/compilation_cache/compile_time_saved_sec":
                self.saved += secs
                return
            else:
                return
            self.per_program[fun_name] = (
                self.per_program.get(fun_name, 0.0) + secs)

        def count(event, **_):
            name = event.rsplit("/", 1)[-1]
            if name in self.cache:
                self.cache[name] += 1

        mon.register_event_time_span_listener(span)
        mon.register_event_duration_secs_listener(duration)
        mon.register_event_listener(count)

    def tracing(self) -> float:
        busy, end = 0.0, None
        for s, e in sorted(self.trace_spans):
            if end is None or s > end:
                busy += e - s
                end = e
            elif e > end:
                busy += e - end
                end = e
        return busy

    def total(self) -> float:
        return self.tracing() + self.lower + self.compile

    def snapshot(self) -> dict:
        top = sorted(self.per_program.items(), key=lambda kv: -kv[1])[:10]
        return {"compile_s": self.total(), "trace_s": self.tracing(),
                "lower_s": self.lower, "backend_compile_s": self.compile,
                "cache_hits": self.cache["cache_hits"],
                "cache_misses": self.cache["cache_misses"],
                "compile_s_saved_by_cache": self.saved,
                "lower_and_compile_by_program": dict(top)}
