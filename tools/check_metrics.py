#!/usr/bin/env python
"""Static metrics lint: every metric declared in drand_tpu/metrics must be
referenced at least once outside its declaration module (no dead
catalogue entries — the `engine_device_batches` regression, ISSUE 1),
metric names must be unique across the four registries (a duplicate
name silently splits one logical series across registries), every
declaration must carry real help text (ISSUE 6: operators read the
catalogue off /metrics), and the engine_op_seconds ``path`` label
values used at the dispatch sites must come from the documented set (a
typo'd path label would silently fork the series operators alert on).

The Grafana dashboard (tools/grafana/drand_tpu.json) is cross-checked
too: every metric its PromQL expressions reference must exist in the
catalogue (counters may appear with the exposition-format ``_total``
suffix, histograms with ``_bucket``/``_sum``/``_count``) — a dashboard
panel silently flat at zero because of a renamed metric is exactly the
failure mode this lint exists to catch.

Run standalone (exit 1 on problems) or from the tier-1 suite
(tests/test_metrics.py::test_metrics_lint) so regressions fail fast.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
METRICS_FILE = REPO / "drand_tpu" / "metrics" / "__init__.py"
DASHBOARD_FILE = REPO / "tools" / "grafana" / "drand_tpu.json"
_METRIC_TYPES = {"Counter", "Gauge", "Histogram", "Summary", "Info"}

# PromQL functions/keywords/aggregators that appear as bare identifiers
# in dashboard expressions and are NOT metric names
_PROMQL_WORDS = {
    "rate", "irate", "increase", "delta", "deriv", "sum", "avg", "min",
    "max", "count", "by", "without", "on", "ignoring", "group_left",
    "group_right", "histogram_quantile", "quantile", "topk", "bottomk",
    "abs", "clamp_min", "clamp_max", "label_replace", "label_join",
    "time", "vector", "scalar", "offset", "and", "or", "unless", "le",
    "bool", "avg_over_time", "max_over_time", "min_over_time",
    "sum_over_time", "count_over_time", "increase_over_time",
}
# exposition-format suffixes prometheus_client appends to the declared
# name (counters -> _total; histograms -> _bucket/_sum/_count)
_SAMPLE_SUFFIXES = ("_total", "_bucket", "_sum", "_count")

# engine_op_seconds base path labels (crypto/batch.py _timed); the
# _error/_invalid suffixes are appended dynamically on failure paths.
# "wire_rlc" is the device wire-pipeline RLC tier (ops/engine.py
# verify_wire_rlc: device hash-to-curve + in-graph lane-MSM, 2 Miller
# pairs per catch-up span); "wire_rlc_sharded" is the same tier with
# the combine sharded over the batch axis of the engine mesh (one
# cross-shard reduction, still one pairing row per span).
# "host_shared" is the timelock round-open host tier
# (crypto/timelock.decrypt_batch: one shared-signature Miller-line
# precomputation for the whole round, per-item evaluation only).
KNOWN_ENGINE_PATHS = {"host", "device", "host_rlc", "wire_rlc",
                      "wire_rlc_sharded", "host_shared"}
# known label VALUES per labelled counter whose cardinality is a fixed
# enum (new values need a deliberate catalogue update here + README)
KNOWN_LABEL_VALUES = {
    # ops/engine.py verify_wire_rlc: one literal per branch, per slice
    "engine_wire_rlc_slices_total": {"prep": {"overlapped", "exposed"}},
    "hash_to_g2_cache_requests": {"result": {"hit", "miss"}},
    "timelock_gt_cache_requests": {"result": {"hit", "miss"}},
    "timelock_ciphertexts_total": {"result": {"submitted", "opened",
                                              "rejected"}},
    # threshold flight recorder (obs/flight.py, ISSUE 10). The `index`
    # label of beacon_partial_events_total is the share index — dynamic
    # but bounded by the group size, so only the `event` enum is pinned
    # here (non-literal label kwargs are invisible to labels_used by
    # design).
    "beacon_partial_arrival_seconds": {"source": {"grpc", "gossip",
                                                  "self"}},
    "beacon_partial_events_total": {"event": {"contributed", "late",
                                              "invalid"}},
    "dkg_phase_seconds": {"phase": {"deal", "response", "justification",
                                    "finish"}},
    # fault-detection set (obs/flight.py reachability, ISSUE 11). The
    # `index` label of beacon_peer_sends_total / beacon_peer_reachable
    # is the share index — dynamic but bounded by the group size (the
    # beacon_partial_events_total rule), so only the `outcome` enum is
    # pinned here.
    "beacon_peer_sends_total": {"outcome": {"ok", "failed"}},
    # the `verdict` label is the handler/gossip rejection string —
    # minted only by code paths (invalid/stale/future/duplicate),
    # passed through a variable so only `source` is literal-checkable
    # here
    "beacon_ingress_rejects_total": {"source": {"grpc", "gossip",
                                                "self"}},
    # self-healing set (ISSUE 12). net_retry_attempts_total's `op` is
    # the call-site tag (partial|sync|repair|control|gossip|timelock) —
    # bounded by the code paths that mint it, passed through the retry
    # helper as a variable, so only `outcome` is literal-checkable.
    "net_retry_attempts_total": {"outcome": {"ok", "retry", "exhausted",
                                             "rejected"}},
    "beacon_partial_repairs_total": {"outcome": {"recovered", "synced",
                                                 "failed"}},
    # edge fan-out set (ISSUE 14): the hub's proto labels are
    # branch-literal (http_server/fanout.py _wakeup_counter), the shed
    # reasons literal at both shed sites, the store backend literal in
    # each backend's read path
    "relay_wakeups_total": {"proto": {"sse", "ndjson"}},
    "relay_shed_total": {"reason": {"watcher_cap", "slow_consumer",
                                    "timelock_slow"}},
    "chain_store_reads_total": {"backend": {"sqlite", "segment"}},
    # timelock at scale (ISSUE 20): vault reads literal in each
    # backend's get() path, notify events branch-literal in
    # TimelockNotifyHub.publish_open
    "vault_reads_total": {"backend": {"sqlite", "segment"}},
    "timelock_notify_total": {"event": {"opened", "rejected"}},
    # incident engine (ISSUE 15): every rule carries its canonical
    # severity at a branch-literal call site (obs/incident.py
    # _incident_counter — the flight.py label-helper pattern); unknown
    # operator rules collapse to rule="custom"
    "incidents_total": {
        "rule": {"missed_round", "readiness_flip", "breaker_open",
                 "reachability_drop", "sync_stall", "margin_degraded",
                 "ingress_flood", "shed_surge", "worker_down", "custom"},
        "severity": {"critical", "major", "warning"},
    },
    # auto-remediation (ISSUE 16): outcomes are branch-literal in
    # obs/remediate.py _action_counter (the `playbook` label there
    # rides a variable — bounded by the playbook registry, the
    # net_retry `op` rule); the active gauge's playbooks ARE
    # branch-literal (_active_gauge), unknown ones collapse to
    # playbook="custom"
    "remediation_actions_total": {
        "outcome": {"ok", "failed", "dry_run", "budget_exhausted",
                    "reverted"},
    },
    "remediation_active": {
        "playbook": {"sync_resume", "quorum_pull", "partition_posture",
                     "respawn_worker", "reshare_recommend", "custom"},
    },
    # million-client catch-up (ISSUE 17): checkpoint bootstrap results
    # are branch-literal in client/verify.py _maybe_bootstrap (ok after
    # the spot-check passes, rejected when the signed checkpoint fails
    # verification and the client falls back to the full walk)
    "checkpoint_bootstraps_total": {"result": {"ok", "rejected"}},
    # large-group ceremonies (ISSUE 19): every phase/verdict pair is
    # branch-literal at its mint site (dkg/protocol.py verification
    # paths + dkg/board.py _accept signature check) — a misbehaving
    # dealer in an n=1024 ceremony is attributable, not silently
    # dropped
    "dkg_bundle_rejects_total": {
        "phase": {"deal", "response", "justification"},
        "verdict": {"bad_signature", "wrong_threshold", "bad_point",
                    "binding_mismatch", "bad_share", "unknown_dealer"},
    },
}


def _declarations() -> list[tuple[str, str, str]]:
    """(python identifier, prometheus name, help text) triples parsed
    from the module-level assignments in drand_tpu/metrics/__init__.py.
    Help is the second positional arg ('' when absent/non-literal)."""
    tree = ast.parse(METRICS_FILE.read_text())
    out: list[tuple[str, str, str]] = []
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        call = node.value
        if not (isinstance(target, ast.Name) and isinstance(call, ast.Call)):
            continue
        fn = call.func
        fn_name = fn.id if isinstance(fn, ast.Name) else getattr(fn, "attr", "")
        if fn_name not in _METRIC_TYPES or not call.args:
            continue
        first = call.args[0]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            continue
        help_text = ""
        if len(call.args) > 1:
            second = call.args[1]
            if isinstance(second, ast.Constant) \
                    and isinstance(second.value, str):
                help_text = second.value
            else:
                # implicit adjacent-literal concatenation parses as a
                # Constant already; anything else (f-string, name) is a
                # lint problem surfaced by the empty help below
                try:
                    help_text = ast.literal_eval(second)
                except (ValueError, SyntaxError):
                    help_text = ""
        out.append((target.id, first.value, help_text))
    return out


def declared_metrics() -> dict[str, str]:
    """python identifier -> prometheus metric name."""
    return {py: name for py, name, _ in _declarations()}


def _corpus() -> str:
    """Every python source that may legitimately reference a metric,
    minus the declaration module itself."""
    parts = []
    for base in ("drand_tpu", "tests", "tools", "scripts"):
        root = REPO / base
        if not root.is_dir():
            continue
        for path in sorted(root.rglob("*.py")):
            if path == METRICS_FILE:
                continue
            parts.append(path.read_text())
    bench = REPO / "bench.py"
    if bench.is_file():
        parts.append(bench.read_text())
    return "\n".join(parts)


def engine_path_labels() -> set[str]:
    """Every literal ``path`` argument handed to crypto/batch.py's
    ``_timed`` dispatch timer (second positional arg)."""
    src = (REPO / "drand_tpu" / "crypto" / "batch.py").read_text()
    out: set[str] = set()
    for node in ast.walk(ast.parse(src)):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "_timed"
                and len(node.args) >= 2):
            continue
        arg = node.args[1]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            out.add(arg.value)
        else:
            out.add("<dynamic>")
    return out


def labels_used(corpus: str, identifier: str) -> dict[str, set[str]]:
    """Literal ``IDENT.labels(key="value")`` kwargs across the corpus."""
    out: dict[str, set[str]] = {}
    pat = rf"\b{re.escape(identifier)}\.labels\(([^)]*)\)"
    for m in re.finditer(pat, corpus):
        for k, v in re.findall(r"(\w+)\s*=\s*[\"']([^\"']+)[\"']",
                               m.group(1)):
            out.setdefault(k, set()).add(v)
    return out


def dashboard_metric_refs(path: pathlib.Path = DASHBOARD_FILE) -> set[str]:
    """Every metric-shaped identifier referenced by the dashboard's
    PromQL expressions. Label selectors ``{...}`` and range selectors
    ``[...]`` are stripped first (their contents are label names/values
    and durations, not metrics); remaining identifiers that are not
    PromQL functions/keywords are metric references — our catalogue
    names all contain '_', which also filters stray words."""
    import json

    doc = json.loads(path.read_text())
    exprs: list[str] = []

    def walk(node):
        if isinstance(node, dict):
            if isinstance(node.get("expr"), str):
                exprs.append(node["expr"])
            for v in node.values():
                walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    walk(doc.get("panels", []))
    refs: set[str] = set()
    for expr in exprs:
        cleaned = re.sub(r"\{[^}]*\}", "", expr)
        cleaned = re.sub(r"\[[^\]]*\]", "", cleaned)
        for tok in re.findall(r"[a-zA-Z_][a-zA-Z0-9_]*", cleaned):
            if tok in _PROMQL_WORDS or "_" not in tok:
                continue
            refs.add(tok)
    return refs


def check_dashboard(decls: dict[str, str]) -> list[str]:
    """Cross-check the Grafana dashboard against the catalogue."""
    if not DASHBOARD_FILE.is_file():
        return [f"dashboard missing: {DASHBOARD_FILE}"]
    try:
        refs = dashboard_metric_refs()
    except ValueError as e:
        return [f"dashboard is not valid JSON: {e}"]
    if not refs:
        return ["dashboard references no metrics (extractor broken?)"]
    known = set(decls.values())
    problems = []
    for ref in sorted(refs):
        candidates = {ref}
        for suf in _SAMPLE_SUFFIXES:
            if ref.endswith(suf):
                candidates.add(ref[: -len(suf)])
        if not candidates & known:
            problems.append(
                f"dashboard references unknown metric {ref!r} "
                f"(tools/grafana/drand_tpu.json vs the catalogue)")
    return problems


def run_lint() -> list[str]:
    """-> list of problems (empty when clean)."""
    problems: list[str] = []
    triples = _declarations()
    decls = {py: name for py, name, _ in triples}
    if not decls:
        return ["no metric declarations found (parser broken?)"]
    seen: dict[str, str] = {}
    for py_name, metric_name, help_text in triples:
        if metric_name in seen:
            problems.append(
                f"duplicate metric name {metric_name!r}: declared as both "
                f"{seen[metric_name]} and {py_name}")
        seen[metric_name] = py_name
        if len(help_text.strip()) < 10:
            problems.append(
                f"{py_name} ({metric_name!r}): missing/too-short help "
                f"text — the catalogue is operator documentation")
    corpus = _corpus()
    for py_name, metric_name in sorted(decls.items()):
        if not re.search(rf"\b{re.escape(py_name)}\b", corpus):
            problems.append(
                f"dead metric: {py_name} ({metric_name!r}) is declared but "
                f"never referenced outside drand_tpu/metrics")
    # engine_op_seconds path labels at the dispatch sites must be from
    # the documented set (suffixes are appended dynamically)
    for path in sorted(engine_path_labels()):
        if path not in KNOWN_ENGINE_PATHS:
            problems.append(
                f"unknown engine_op_seconds path label {path!r} in "
                f"crypto/batch.py (known: {sorted(KNOWN_ENGINE_PATHS)})")
    # fixed-enum label values: literal uses must be in the catalogue
    name_to_py = {v: k for k, v in decls.items()}
    for metric_name, expected in KNOWN_LABEL_VALUES.items():
        py_name = name_to_py.get(metric_name)
        if py_name is None:
            problems.append(
                f"KNOWN_LABEL_VALUES names undeclared metric "
                f"{metric_name!r}")
            continue
        used = labels_used(corpus, py_name)
        if not used:
            # a configured metric with zero literal label uses means the
            # check validates nothing — e.g. values routed through a
            # wrapper variable; keep call-site values literal instead
            problems.append(
                f"{metric_name}: no literal .labels(...) uses found — "
                f"the KNOWN_LABEL_VALUES lint cannot validate it")
        for key, values in used.items():
            bad = values - expected.get(key, set())
            if bad:
                problems.append(
                    f"{metric_name}: unexpected {key} label value(s) "
                    f"{sorted(bad)} (known: {sorted(expected.get(key, set()))})")
    problems.extend(check_dashboard(decls))
    return problems


def main() -> int:
    problems = run_lint()
    for p in problems:
        print(f"check_metrics: {p}", file=sys.stderr)
    if not problems:
        print(f"check_metrics: OK ({len(declared_metrics())} metrics, "
              f"all referenced, names unique)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
