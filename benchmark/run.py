#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its per-layer metrics are
found by name: ``BENCHMARK.json`` names the configuration file and the
traffic (``benchmark/traffic/<traffic>.json``); the traffic's ``loop``
names the driver (``benchmark/drivers/<loop>.py``); each per-layer metric
is read by ``benchmark/layers/<metric>.py``. Adding a cell, a traffic mix
or a metric adds files and entries only.

A run: find the chip (none, or too few: exit 1 before any result), warm
every shape the cell's traffic uses (set-up, ``setup_s``), measure for
``--seconds`` with the profiler off (``--trace 0``: the end-to-end
metrics) or on (``--trace 1``: the per-layer metrics), then check what
the timed path produced against the plain reference and print the last
line. ``--fault``, not used by the benchmark's own runs, plants a fault
or the control (``benchmark/faults/<name>.py``).
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
# JAX's persistent compilation cache: a fixed directory of the checkout
# that only the benchmark writes
CACHE_DIR = ".bench_jax_cache"


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_module(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str) -> dict:
    bench = load_json("BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return {"bench": bench, "workload": wl,
            "config": load_json(cfg_entry["file"]),
            "traffic": load_json(f"benchmark/traffic/{wl['traffic']}.json")}


def cell_metrics(cell: dict, kind: str) -> list[dict]:
    """The cell's metrics of ``kind`` (end_to_end / per_layer)."""
    name = cell["workload"]["name"]
    e2e = [m for m in cell["bench"]["end_to_end"]
           if name in m.get("workloads", [name])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in cell["bench"]["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in moved
                             else [])]


def find_chip(chips: int) -> dict:
    """The device as JAX reports it; SystemExit when it is not a TPU with
    at least ``chips`` chips, or its kind has no entry in the peaks table."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] < chips:
        raise SystemExit(f"need {chips} TPU chip(s); JAX found "
                         f"{dev['count']} {dev['platform']} device(s)")
    if dev["kind"] not in load_json("benchmark/peaks.json"):
        raise SystemExit(f"device kind {dev['kind']!r} is not in "
                         f"benchmark/peaks.json")
    return dev


def memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def read_layers(cell: dict, ctx: dict) -> dict:
    out = {}
    for m in cell_metrics(cell, "per_layer"):
        mod = load_module(f"benchmark/layers/{m['name']}.py",
                          "layer_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(argv=None, *, chip: bool = True, dispatch: str = "device",
        traffic: dict | None = None) -> dict:
    """Set up, measure the seed; returns the result line. Tests
    pass ``chip=False`` (no look for a chip), a host ``dispatch`` and a
    small ``traffic``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="",
                    help="plant benchmark/faults/<name>.py")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, CACHE_DIR)
    cell = find_cell(args.workload)
    if traffic is not None:
        cell["traffic"] = traffic
    driver = load_module(f"benchmark/drivers/{cell['traffic']['loop']}.py",
                         "driver_" + cell["traffic"]["loop"])

    from harness.compile_clock import CompileClock

    clock = CompileClock()
    dev = (find_chip(cell["workload"]["chips"]) if chip
           else {"platform": "cpu", "kind": "cpu", "count": 1})

    from drand_tpu.crypto import batch
    from drand_tpu.utils.jit_cache import enable_persistent_cache

    enable_persistent_cache()
    batch.configure(dispatch)
    if args.fault:
        load_module(f"benchmark/faults/{args.fault}.py", "fault").plant()
    session = driver.Session(cell, ROOT)
    try:
        session.warm()
        setup_s = time.monotonic() - T0
        print(json.dumps({"setup": {"setup_s": setup_s, **clock.snapshot()},
                          "workload": args.workload}), flush=True)
        return _measure(cell, session, clock, dev, args, setup_s)
    finally:
        session.close()


def _measure(cell, session, clock, dev, args, setup_s) -> dict:
    from harness import counters, trace

    before = counters.snapshot()
    c0 = clock.total()
    red = None
    if args.trace:
        holder = {}
        with trace.capture(holder):
            rec = session.window(args.seed, args.seconds)
        red = trace.reduce(holder["trace"])
    else:
        rec = session.window(args.seed, args.seconds)
    compile_in_window = clock.total() - c0
    after = counters.snapshot()
    device = dict(dev, memory_peak_bytes=memory_peak())
    print(json.dumps({"window": {k: v for k, v in rec.items()
                                 if not k.startswith("_")},
                      "compile_s_in_window": compile_in_window,
                      "seed": args.seed}),
          flush=True)
    compared = session.check(rec, args.seed)
    ctx = {"window_s": rec["window_s"], "work": rec["work"],
           "counters": (before, after), "trace": red}
    if args.trace:
        metrics = read_layers(cell, ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    else:
        metrics = {}
        for m in cell_metrics(cell, "end_to_end"):
            if m["name"] == "setup_s":
                v = setup_s
            elif m["name"] == cell["traffic"]["rate_metric"]:
                v = rec["work"] / rec["window_s"]
            else:
                raise SystemExit(f"no source for metric {m['name']!r}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": all(c["value"] <= c["limit"] for c in compared.values()),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    if red is not None:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    try:
        out = run(argv)
    except SystemExit as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    except Exception:  # noqa: BLE001 — no result line, a non-zero exit
        traceback.print_exc()
        return 1
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
