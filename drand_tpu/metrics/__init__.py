"""Prometheus metrics — the reference catalogue, four registries.

Reference: metrics/metrics.go:17-146 defines four registries
(PrivateMetrics :17, HTTPMetrics :20, GroupMetrics :22, ClientMetrics
:24) and the catalogue below; client/http/metric.go:14 adds the client
heartbeat set. The store decorator feeding the beacon gauges is
chain/beacon/store.go:57 (discrepancyStore → our DiscrepancyStore).

Catalogue parity (reference name → here):
  api_call_counter               → api_call_counter           [private]
  dial_failures                  → outgoing_connection_failures [group]
  group_connections              → group_connections          [group]
  beacon_discrepancy_latency     → beacon_discrepancy_latency_ms [group]
  last_beacon_round              → last_beacon_round          [group]
  http_call_counter              → http_api_requests          [http]
  http_response_duration         → http_api_latency_seconds   [http]
  http_in_flight                 → http_in_flight             [http]
  client_watch_latency           → client_watch_latency       [client]
  client_http_heartbeat_success  → client_http_heartbeat_success [client]
  client_http_heartbeat_failure  → client_http_heartbeat_failure [client]
  client_http_heartbeat_latency  → client_http_heartbeat_latency [client]
  client_in_flight               → client_in_flight           [client]
  client_api_requests_total      → client_api_requests_total  [client]
  client_request_duration_seconds→ client_request_duration_seconds [client]
  (client_dns/tls_duration_seconds are Go httptrace hooks with no
   asyncio equivalent — intentionally absent)
Additions beyond the reference (the TPU engine + round tracing):
  engine_device_batches, engine_device_fallbacks, dkg_bundles_received
  beacon_stage_seconds{stage}          [group]   per-stage round latency,
      fed by the obs tracing spans (obs/trace.py) — partial, collect,
      recover, verify, store, sync_verify, gossip_validate, breather
  engine_op_seconds{op,path,batch}     [private] per-op device-vs-host
      latency, batch-size-bucketed (crypto/batch.py dispatch wrappers);
      path="host_rlc" marks the randomized-linear-combination batch
      verifier (crypto/batch_verify.py — one 2-pairing product check
      for a whole span instead of one per item); path="wire_rlc" the
      device wire-pipeline RLC tier (ops/engine.py verify_wire_rlc —
      device hash-to-curve + in-graph lane-MSM, 2 Miller pairs per
      catch-up span with no host hashing)
  engine_wire_rlc_slices_total{prep}   [private] wire-RLC combine slices
      launched while the span's previous slice was still running on the
      device (overlapped) or with the device idle (exposed: a span's
      first, or a device that finished before the host)
  hash_to_g2_cache_requests{result}    [private] hash-to-G2 memo
      hit/miss counters (crypto/hash_to_curve.py per-round keyed LRU)
Timelock serving tier (drand_tpu/timelock, ISSUE 9):
  timelock_gt_cache_requests{result}   [private] encrypt-side per-round
      e(pub, H2(round)) base memo hit/miss (crypto/timelock.py)
  timelock_pending_ciphertexts         [private] vault backlog waiting
      for a future round's V2 signature
  timelock_ciphertexts_total{result}   [private] vault lifecycle counter
      (submitted | opened | rejected); round-open latency rides
      engine_op_seconds{op="timelock", path=device|host_shared}
  timelock_open_dispatches_total       [private] chunked boundary-open
      dispatches — ceil(K/DRAND_TPU_TIMELOCK_OPEN_CHUNK) per round of K
      pending ciphertexts (ISSUE 20 bounded opens)
  timelock_sweep_shards                [private] token-range shards the
      boundary sweep partitions over (1 = sole sweeper, K = one of a
      relay --workers K group each opening a disjoint shard)
  vault_reads_total{backend}           [private] vault record reads by
      backend (sqlite | segment) — segment-vault migration
      observability (ISSUE 20)
Chain-health / SLO set (obs/health.py, ISSUE 6 — fed by the
DiscrepancyStore on every stored beacon and re-evaluated by /healthz):
  beacon_round_lateness_seconds        [group]   actual emit time vs the
      scheduled round boundary, per stored round
  chain_head_round                     [group]   last stored round
  chain_head_lag_rounds                [group]   expected round - head
  beacon_rounds_missed_total           [group]   rounds whose whole
      period passed with no stored beacon (counted once per round)
  beacon_slo_late_fraction             [group]   sliding-window fraction
      of rounds late by more than period/2
  chain_sync_rounds_per_second         [group]   follow_chain catch-up
      throughput (0 when no follow is running)
  chain_sync_eta_seconds               [group]   follow_chain ETA to the
      target round (-1 = unbounded follow, 0 = idle/done)
Threshold flight recorder (obs/flight.py, ISSUE 10 — fed by partial
ingress, the aggregator, gossip validation and the DKG protocol):
  beacon_quorum_margin_seconds         [group]   period minus the
      arrival offset of the t-th valid partial — the distance-to-
      missed-round early-warning SLI (negative = quorum after the
      round's whole period had already passed)
  beacon_partial_arrival_seconds{source} [group] valid partial/beacon
      arrival offset from the round boundary by ingress source
      (grpc | gossip | self)
  beacon_partial_events_total{index,event} [group] per-share-index
      contribution/lateness/invalid counters (event: contributed |
      late | invalid; late = arrived more than period/2 after the
      boundary; index cardinality is bounded by the group size)
  beacon_contribution_gap              [group]   group size minus the
      distinct valid contributors of the last stored round
  dkg_phase_seconds{phase}             [group]   DKG/reshare phase
      durations (deal | response | justification | finish)
Fault-detection set (obs/flight.py reachability + obs/health.py stall
detection, ISSUE 11 — the chaos simulator's oracle for faults the
ISSUE-6/10 SLIs could not see; fed by the handler's outbound partial
sends and the /healthz pull path):
  beacon_peer_reachable{index}         [group]   1 while the last
      outbound send to that group member succeeded, 0 after a failure
      (index cardinality bounded by the group size, like
      beacon_partial_events_total)
  beacon_partition_suspects            [group]   count of group peers
      currently unreachable from this node — when it reaches
      n - threshold the node itself can no longer see a quorum
  beacon_peer_sends_total{index,outcome} [group] outbound
      partial-broadcast attempts per peer by outcome (ok | failed)
  beacon_ingress_rejects_total{source,verdict} [group] partial/beacon
      ingress rejections by ingress source and verdict (invalid |
      stale | future | duplicate) — the flood/abuse signal the
      per-peer counters cannot carry (window rejects and garbage
      prefixes are deliberately never attributed to a peer)
  chain_sync_stalled                   [group]   1 while the chain lags
      beyond the readiness bound with no catch-up making progress
      (pull-model: re-evaluated by /healthz probes and scrapes)
Self-healing set (utils/retry.py policy, net/transport.py breakers,
handler quorum repair, http_server stale serving — ISSUE 12: the
active-recovery tier the ISSUE-11 fault oracle proved was missing):
  net_retry_attempts_total{op,outcome} [group]   every retry-policy
      attempt by call-site op (partial | sync | repair | control |
      gossip | timelock | watch) and outcome (ok | retry | exhausted |
      rejected — rejected = classified non-retryable, e.g. the peer
      answered with a reject)
  beacon_peer_breaker_state{index}     [group]   per-peer circuit
      breaker state (0 = closed, 1 = half-open, 2 = open); fed by the
      same outbound-send outcomes as beacon_peer_reachable, index
      cardinality bounded by the group size
  beacon_partial_repairs_total{outcome} [group]  quorum-repair
      operations by outcome (recovered = the pull reached threshold
      inside the round's period; synced = peers already stored the
      round, fetched via sync instead; failed = still below threshold)
  relay_stale_served_total             [http]    /public/latest
      responses served from the last-known beacon with the
      X-Drand-Stale header because the upstream was unreachable
Incident engine (obs/incident.py + obs/timeseries.py, ISSUE 15 — the
anomaly rules evaluated on every SLI time-series sample, minting
incidents with frozen forensic bundles):
  incidents_total{rule,severity}       [group]   incidents minted by
      the detector, by rule (missed_round | readiness_flip |
      breaker_open | reachability_drop | sync_stall |
      margin_degraded | ingress_flood | shed_surge | custom) and
      severity (critical | major | warning) — one per SUSTAINED fault
      (re-fires extend the open incident, cooldown suppresses flaps)
  incident_active                      [group]   currently open
      incidents (their rules still firing or not yet cleared)
Auto-remediation (obs/remediate.py, ISSUE 16 — the closed loop:
incidents drive guardrailed playbooks, every attempt audited):
  remediation_actions_total{playbook,outcome} [group]  remediation-
      ledger entries by playbook (sync_resume | quorum_pull |
      partition_posture | respawn_worker | reshare_recommend) and
      outcome (ok | failed | dry_run | budget_exhausted | reverted) —
      dry_run is the default posture until DRAND_TPU_REMEDIATE=live
  remediation_active{playbook}         [group]   playbooks holding an
      action in flight or a sticky posture (partition_posture stays 1
      until its incident closes and the revert runs)
  remediation_mttr_seconds             [group]   open-to-close of
      incidents the engine acted on — MTTR as a measured SLI
Edge fan-out set (http_server/fanout.py hub + chain/segments.py,
ISSUE 14 — the push tier on /public/latest and the packed segment
chain store behind it):
  relay_watchers                       [http]    currently connected
      /public/latest stream watchers (SSE + NDJSON) on this worker
  relay_wakeups_total{proto}           [http]    hub publishes that woke
      at least one watcher of that protocol (sse | ndjson) — ≤1 per
      round per protocol per worker, NOT O(watchers); the push-tier
      cost model in one counter
  relay_shed_total{reason}             [http]    watcher connections
      refused or dropped by the load shedder (watcher_cap = 429 at the
      connection cap with Retry-After on the next round boundary;
      slow_consumer = bounded send queue overflowed, the stream was
      disconnected rather than buffered unboundedly; timelock_slow =
      the same queue overflow on the /timelock open-notify leg)
  timelock_watchers                    [http]    currently connected
      /timelock open-notify stream watchers (SSE + NDJSON) on this
      worker (ISSUE 20)
  timelock_notify_total{event}         [http]    decided-ciphertext
      events pushed on the /timelock leg (opened | rejected), once per
      ciphertext regardless of watcher count
  relay_boundary_delivery_seconds      [http]    scheduled round
      boundary to hub publish on this worker — the server half of
      boundary-to-delivery latency (the bench measures the client half)
  chain_store_reads_total{backend}     [group]   beacon reads served by
      the chain store by backend (sqlite | segment) — the migration
      observability for the packed segment format
Million-client catch-up (client/verify.py + client/checkpoint.py,
ISSUE 17 — RLC span verification, pipelined fetch/verify and signed
checkpoint trust):
  client_catchup_rounds_total          [client]  rounds verified by the
      VerifyingClient catch-up walk (every beacon that passed an RLC
      span check or per-item fallback)
  client_catchup_chunk_rounds          [client]  current adaptive
      catch-up chunk size — grows geometrically toward
      DRAND_TPU_CATCHUP_CHUNK_MAX while spans verify clean, halves on
      a corrupt span
  checkpoint_bootstraps_total{result}  [client]  checkpoint trust
      bootstraps by result (ok = verified + spot-checked, trust jumped
      to the checkpoint round; rejected = the signed checkpoint failed
      verification and the client fell back to the full walk)
  checkpoint_issued_total              [group]   checkpoints recovered
      by the aggregator from piggybacked threshold partials
  checkpoint_round                     [group]   round of the latest
      recovered checkpoint served at /checkpoints/latest
Engine introspection (ISSUE 6):
  engine_compile_seconds{op}           [private] FIRST dispatch of each
      (op, path, batch-bucket) device shape — the jit compile +
      first-run cost, split out so steady-state engine_op_seconds
      percentiles stay clean (crypto/batch.py _timed)
  otlp_export_rounds_total{sink}       [private] round traces exported
      by the OTLP exporter, by sink (http|spool|dropped)

Everything is exposed on /metrics (render() gathers all four registries
— the reference's handler chains its gatherers the same way,
metrics.go:229) and relayed per peer via /peer/{addr}/metrics.
"""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

# Four registries (metrics.go:17-24). REGISTRY keeps its old name as the
# private/default one for back-compat with existing callers.
REGISTRY = CollectorRegistry()          # PrivateMetrics
HTTP_REGISTRY = CollectorRegistry()     # HTTPMetrics
GROUP_REGISTRY = CollectorRegistry()    # GroupMetrics
CLIENT_REGISTRY = CollectorRegistry()   # ClientMetrics

# ---- private (node-to-node API) -------------------------------------------
API_CALLS = Counter(
    "api_call_counter", "Private gRPC API calls", ["method"],
    registry=REGISTRY)

# ---- group (chain + mesh health) ------------------------------------------
BEACON_DISCREPANCY_LATENCY = Gauge(
    "beacon_discrepancy_latency_ms",
    "Milliseconds between the expected round time and the beacon being stored",
    registry=GROUP_REGISTRY)
LAST_BEACON_ROUND = Gauge(
    "last_beacon_round", "Last aggregated and stored beacon round",
    registry=GROUP_REGISTRY)
DIAL_FAILURES = Counter(
    "outgoing_connection_failures",
    "Failed outbound node-to-node calls", ["peer"],
    registry=GROUP_REGISTRY)
GROUP_CONNECTIONS = Gauge(
    "group_connections", "Open outbound connections to group members",
    registry=GROUP_REGISTRY)
DKG_BUNDLES = Counter(
    "dkg_bundles_received", "DKG bundles accepted by the broadcast board",
    ["kind"], registry=GROUP_REGISTRY)

DKG_BUNDLE_REJECTS = Counter(
    "dkg_bundle_rejects_total",
    "DKG bundles/items rejected during verification, by phase and "
    "verdict (bad_signature|wrong_threshold|bad_point|binding_mismatch|"
    "bad_share|unknown_dealer) — a misbehaving dealer in a large-group "
    "ceremony is attributable, not silently dropped",
    ["phase", "verdict"], registry=GROUP_REGISTRY)

# ---- http (public REST server) --------------------------------------------
HTTP_REQUESTS = Counter(
    "http_api_requests", "Public REST API calls", ["path", "code"],
    registry=HTTP_REGISTRY)
HTTP_LATENCY = Histogram(
    "http_api_latency_seconds", "Public REST API latency", ["path"],
    registry=HTTP_REGISTRY)
HTTP_IN_FLIGHT = Gauge(
    "http_in_flight", "In-flight public REST requests",
    registry=HTTP_REGISTRY)

# ---- client (the consuming side: watches, heartbeats) ---------------------
CLIENT_WATCH_LATENCY = Gauge(
    "client_watch_latency",
    "Duration between time round received and time round expected (ms)",
    registry=CLIENT_REGISTRY)
CLIENT_HEARTBEAT_SUCCESS = Counter(
    "client_http_heartbeat_success", "Successful client heartbeats",
    ["url"], registry=CLIENT_REGISTRY)
CLIENT_HEARTBEAT_FAILURE = Counter(
    "client_http_heartbeat_failure", "Failed client heartbeats",
    ["url"], registry=CLIENT_REGISTRY)
CLIENT_HEARTBEAT_LATENCY = Gauge(
    "client_http_heartbeat_latency", "Last client heartbeat latency (s)",
    ["url"], registry=CLIENT_REGISTRY)
CLIENT_IN_FLIGHT = Gauge(
    "client_in_flight", "In-flight client requests per url",
    ["url"], registry=CLIENT_REGISTRY)
CLIENT_REQUESTS = Counter(
    "client_api_requests_total", "Client requests by url and outcome",
    ["url", "code"], registry=CLIENT_REGISTRY)
CLIENT_REQUEST_DURATION = Histogram(
    "client_request_duration_seconds", "Client request latency",
    ["url"], registry=CLIENT_REGISTRY)

# ---- engine (no reference counterpart: the TPU compute path) --------------
ENGINE_BATCHES = Counter(
    "engine_device_batches", "Batched device crypto calls", ["op"],
    registry=REGISTRY)
ENGINE_FALLBACKS = Counter(
    "engine_device_fallbacks", "Device-engine failures that fell back to host",
    registry=REGISTRY)
ENGINE_WIRE_RLC_SLICES = Counter(
    "engine_wire_rlc_slices_total",
    "Wire-RLC combine slices by host preparation (ops/engine.py "
    "verify_wire_rlc): overlapped = launched while the span's previous "
    "slice was still running on the device; exposed = launched with "
    "the device idle (a span's first slice, or a device that finished "
    "the previous slice before the host finished this one)",
    ["prep"], registry=REGISTRY)
H2C_CACHE_REQUESTS = Counter(
    "hash_to_g2_cache_requests",
    "hash_to_g2 memo lookups by result (hit|miss) — the per-round "
    "hash-to-curve LRU in crypto/hash_to_curve.py",
    ["result"], registry=REGISTRY)

# ---- timelock serving tier (drand_tpu/timelock, ISSUE 9) ------------------
TIMELOCK_GT_CACHE_REQUESTS = Counter(
    "timelock_gt_cache_requests",
    "timelock encrypt GT-base memo lookups by result (hit|miss) — the "
    "per-round e(pub, H2(round)) LRU in crypto/timelock.py",
    ["result"], registry=REGISTRY)
TIMELOCK_PENDING = Gauge(
    "timelock_pending_ciphertexts",
    "Ciphertexts in the timelock vault still waiting for their round's "
    "V2 signature", registry=REGISTRY)
TIMELOCK_CIPHERTEXTS = Counter(
    "timelock_ciphertexts_total",
    "Timelock vault ciphertext lifecycle events by result (submitted = "
    "accepted into the vault; opened = decrypted at the round boundary; "
    "rejected = failed the Fujisaki-Okamoto check or could never open)",
    ["result"], registry=REGISTRY)
TIMELOCK_OPEN_DISPATCHES = Counter(
    "timelock_open_dispatches_total",
    "Chunked round-boundary open dispatches — one shared-signature "
    "batched decrypt per chunk of at most DRAND_TPU_TIMELOCK_OPEN_CHUNK "
    "pending ciphertexts, so a round of K opens in ceil(K/chunk) "
    "dispatches with a vault commit and a cooperative yield after each",
    registry=REGISTRY)
TIMELOCK_SWEEP_SHARDS = Gauge(
    "timelock_sweep_shards",
    "Token-range shard count this worker's boundary sweep partitions "
    "over (1 = sole sweeper; K = one of a relay --workers K group, "
    "each opening a disjoint token shard of every round)",
    registry=REGISTRY)
VAULT_READS = Counter(
    "vault_reads_total",
    "Timelock vault record reads (status lookups and submit "
    "idempotency probes) by backend (sqlite|segment) — the migration "
    "observability for the segment vault format",
    ["backend"], registry=REGISTRY)

# ---- round tracing (obs/trace.py) -----------------------------------------
# Stage/op work spans sub-millisecond (host crypto on small groups) to
# tens of seconds (cold-compile device dispatches) — the default
# prometheus buckets start too coarse at the low end.
_LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
BEACON_STAGE_SECONDS = Histogram(
    "beacon_stage_seconds",
    "Per-stage beacon round-lifecycle latency (obs tracing spans)",
    ["stage"], registry=GROUP_REGISTRY, buckets=_LATENCY_BUCKETS)
ENGINE_OP_SECONDS = Histogram(
    "engine_op_seconds",
    "Batched crypto op latency by path (device|host; failed dispatches "
    "land under <path>_error) and batch bucket",
    ["op", "path", "batch"], registry=REGISTRY, buckets=_LATENCY_BUCKETS)
ENGINE_COMPILE_SECONDS = Histogram(
    "engine_compile_seconds",
    "First dispatch of each (op, batch-bucket) device shape — jit "
    "compile + first run, split from steady-state engine_op_seconds",
    ["op"], registry=REGISTRY,
    buckets=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0))

# ---- chain health / SLOs (obs/health.py) ----------------------------------
# Lateness spans "on time" (ms after the boundary) to "a whole period
# late"; the SLO threshold is period/2, so the buckets must resolve
# fractions of typical periods (3-30 s).
_LATENESS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 15.0,
                     30.0, 60.0, 120.0)
BEACON_LATENESS = Histogram(
    "beacon_round_lateness_seconds",
    "Actual beacon emit time minus the scheduled round boundary",
    registry=GROUP_REGISTRY, buckets=_LATENESS_BUCKETS)
CHAIN_HEAD_ROUND = Gauge(
    "chain_head_round", "Last beacon round stored on this node's chain",
    registry=GROUP_REGISTRY)
CHAIN_HEAD_LAG = Gauge(
    "chain_head_lag_rounds",
    "Rounds between the wall-clock expected round and the stored head",
    registry=GROUP_REGISTRY)
MISSED_ROUNDS = Counter(
    "beacon_rounds_missed_total",
    "Rounds whose whole period elapsed with no beacon stored "
    "(counted once per skipped round; a later catch-up does not uncount)",
    registry=GROUP_REGISTRY)
SLO_LATE_FRACTION = Gauge(
    "beacon_slo_late_fraction",
    "Fraction of the sliding round window emitted later than period/2 "
    "after their boundary (the chain-health SLO)",
    registry=GROUP_REGISTRY)
SYNC_ROUNDS_PER_SEC = Gauge(
    "chain_sync_rounds_per_second",
    "follow_chain catch-up throughput over the current follow "
    "(0 when idle)", registry=GROUP_REGISTRY)
SYNC_ETA_SECONDS = Gauge(
    "chain_sync_eta_seconds",
    "Estimated seconds until follow_chain reaches its target round "
    "(-1 for an unbounded follow, 0 when idle/done)",
    registry=GROUP_REGISTRY)

# ---- threshold flight recorder (obs/flight.py) ----------------------------
# Margin spans "quorum landed instantly" (≈ period) down through "barely
# made it" (≈ 0) to "quorum after the period elapsed" (negative) — the
# negative buckets keep a dying group's rounds distinguishable from
# healthy instant-quorum ones.
_MARGIN_BUCKETS = (-60.0, -10.0, -1.0, 0.0, 0.25, 0.5, 1.0, 2.5, 5.0,
                   10.0, 15.0, 30.0, 60.0)
QUORUM_MARGIN = Histogram(
    "beacon_quorum_margin_seconds",
    "Round period minus the time-to-t-th-valid-partial — how far the "
    "round stayed from missing quorum (the early-warning SLI)",
    registry=GROUP_REGISTRY, buckets=_MARGIN_BUCKETS)
PARTIAL_ARRIVAL = Histogram(
    "beacon_partial_arrival_seconds",
    "Valid partial/beacon arrival offset from the scheduled round "
    "boundary, by ingress source (grpc|gossip|self)",
    ["source"], registry=GROUP_REGISTRY, buckets=_LATENESS_BUCKETS)
PARTIAL_EVENTS = Counter(
    "beacon_partial_events_total",
    "Per-share-index partial-signature events (contributed = valid "
    "partial accepted; late = valid but more than period/2 after the "
    "boundary; invalid = failed verification/window checks)",
    ["index", "event"], registry=GROUP_REGISTRY)
CONTRIBUTION_GAP = Gauge(
    "beacon_contribution_gap",
    "Group size minus the distinct valid contributors of the last "
    "stored round (0 = full participation)",
    registry=GROUP_REGISTRY)
DKG_PHASE_SECONDS = Histogram(
    "dkg_phase_seconds",
    "DKG/reshare phase durations by phase "
    "(deal|response|justification|finish)",
    ["phase"], registry=GROUP_REGISTRY,
    buckets=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0))

# ---- fault detection (obs/flight.py reachability, obs/health.py) ----------
PEER_REACHABLE = Gauge(
    "beacon_peer_reachable",
    "1 while the last outbound partial send to this group member "
    "succeeded, 0 after a send failure (per share index)",
    ["index"], registry=GROUP_REGISTRY)
PARTITION_SUSPECTS = Gauge(
    "beacon_partition_suspects",
    "Group peers currently unreachable from this node by outbound send "
    "result — at n minus threshold the node cannot see a quorum "
    "(the partition-suspect early warning)",
    registry=GROUP_REGISTRY)
PEER_SENDS = Counter(
    "beacon_peer_sends_total",
    "Outbound partial-broadcast attempts per group member by outcome "
    "(ok = delivered; failed = transport error / unreachable)",
    ["index", "outcome"], registry=GROUP_REGISTRY)
INGRESS_REJECTS = Counter(
    "beacon_ingress_rejects_total",
    "Partial/beacon ingress rejections by source (grpc|gossip|self) "
    "and verdict (invalid|stale|future|duplicate) — the flood/abuse "
    "visibility the peer-attributed counters deliberately do not carry",
    ["source", "verdict"], registry=GROUP_REGISTRY)
SYNC_STALLED = Gauge(
    "chain_sync_stalled",
    "1 while the chain head lags beyond the readiness bound and no "
    "catch-up is making progress (re-evaluated by /healthz and scrapes)",
    registry=GROUP_REGISTRY)

# ---- self-healing (utils/retry.py, net/transport.py, handler repair) ------
NET_RETRY_ATTEMPTS = Counter(
    "net_retry_attempts_total",
    "Retry-policy attempts by call-site op (partial|sync|repair|"
    "control|gossip|timelock|watch) and outcome (ok = attempt "
    "succeeded; "
    "retry = failed with a backoff sleep following; exhausted = failed "
    "with no budget left; rejected = classified non-retryable)",
    ["op", "outcome"], registry=GROUP_REGISTRY)
PEER_BREAKER_STATE = Gauge(
    "beacon_peer_breaker_state",
    "Per-peer circuit breaker state by share index "
    "(0 = closed, 1 = half-open, 2 = open) — open means outbound "
    "sends to that member are skipped until the next capped probe",
    ["index"], registry=GROUP_REGISTRY)
PARTIAL_REPAIRS = Counter(
    "beacon_partial_repairs_total",
    "Quorum-repair operations by outcome (recovered = the pull "
    "reached the threshold inside the round's period; synced = peers "
    "had already stored the round, the beacon is fetched via sync "
    "instead; failed = the round stayed below threshold)",
    ["outcome"], registry=GROUP_REGISTRY)
# ---- incident engine (obs/incident.py, ISSUE 15) --------------------------
INCIDENTS_TOTAL = Counter(
    "incidents_total",
    "Incidents minted by the anomaly detector over the SLI time-series "
    "ring, by rule and severity — one per sustained fault (re-fires "
    "extend the open incident; the per-rule cooldown suppresses flaps)",
    ["rule", "severity"], registry=GROUP_REGISTRY)
INCIDENT_ACTIVE = Gauge(
    "incident_active",
    "Currently open incidents: their rules are still firing or have "
    "not yet stayed quiet for the clear window",
    registry=GROUP_REGISTRY)
# ---- auto-remediation (obs/remediate.py, ISSUE 16) ------------------------
REMEDIATION_ACTIONS = Counter(
    "remediation_actions_total",
    "Remediation-ledger entries by playbook and outcome (ok = action "
    "ran / recommendation written; failed = action raised; dry_run = "
    "engine not armed, annotated what it WOULD do; budget_exhausted = "
    "the global actions-per-window budget refused it; reverted = a "
    "sticky playbook's revert ran when its incident closed)",
    ["playbook", "outcome"], registry=GROUP_REGISTRY)
REMEDIATION_ACTIVE = Gauge(
    "remediation_active",
    "Playbooks currently holding an action in flight or a sticky "
    "posture (1 while held; partition_posture stays 1 until the "
    "reachability incident closes and the revert restores the caps)",
    ["playbook"], registry=GROUP_REGISTRY)
_MTTR_BUCKETS = (1.0, 5.0, 15.0, 30.0, 60.0, 120.0, 300.0, 600.0,
                 1800.0)
REMEDIATION_MTTR = Histogram(
    "remediation_mttr_seconds",
    "Open-to-close duration of incidents the remediation engine acted "
    "on — mean time to recovery as a first-class SLI",
    registry=GROUP_REGISTRY, buckets=_MTTR_BUCKETS)

RELAY_STALE_SERVED = Counter(
    "relay_stale_served_total",
    "/public/latest responses served from the last-known beacon with "
    "the X-Drand-Stale header because the upstream was unreachable",
    registry=HTTP_REGISTRY)

# ---- edge fan-out push tier (http_server/fanout.py, ISSUE 14) -------------
RELAY_WATCHERS = Gauge(
    "relay_watchers",
    "Currently connected /public/latest stream watchers (SSE + NDJSON) "
    "on this relay worker process",
    registry=HTTP_REGISTRY)
RELAY_WAKEUPS = Counter(
    "relay_wakeups_total",
    "Fan-out hub publishes that woke at least one watcher, by stream "
    "protocol (sse|ndjson) — at most one per round per protocol per "
    "worker regardless of watcher count",
    ["proto"], registry=HTTP_REGISTRY)
RELAY_SHED = Counter(
    "relay_shed_total",
    "Stream watchers refused or dropped by the load shedder "
    "(watcher_cap = 429 at the connection cap, Retry-After on the next "
    "round boundary; slow_consumer = bounded send queue overflowed and "
    "the stream was disconnected; timelock_slow = same overflow on the "
    "/timelock open-notify leg)",
    ["reason"], registry=HTTP_REGISTRY)
TIMELOCK_WATCHERS = Gauge(
    "timelock_watchers",
    "Currently connected /timelock open-notify stream watchers "
    "(SSE + NDJSON) on this worker",
    registry=HTTP_REGISTRY)
TIMELOCK_NOTIFY = Counter(
    "timelock_notify_total",
    "Open-notify events published on the /timelock stream leg by "
    "terminal status (opened|rejected) — counted once per decided "
    "ciphertext, not per watcher",
    ["event"], registry=HTTP_REGISTRY)
RELAY_BOUNDARY_DELIVERY = Histogram(
    "relay_boundary_delivery_seconds",
    "Scheduled round boundary to fan-out hub publish on this worker "
    "(the server half of boundary-to-delivery latency)",
    registry=HTTP_REGISTRY,
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
             0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0))
CHAIN_STORE_READS = Counter(
    "chain_store_reads_total",
    "Beacon reads served by the chain store, by backend "
    "(sqlite|segment) — get() and cursor batches both count per beacon",
    ["backend"], registry=GROUP_REGISTRY)

# ---- million-client catch-up (client/verify.py, ISSUE 17) -----------------
CLIENT_CATCHUP_ROUNDS = Counter(
    "client_catchup_rounds_total",
    "Rounds verified by the VerifyingClient catch-up walk (RLC span "
    "checks plus per-item fallbacks both count per beacon)",
    registry=CLIENT_REGISTRY)
CLIENT_CATCHUP_CHUNK = Gauge(
    "client_catchup_chunk_rounds",
    "Current adaptive catch-up chunk size — grows geometrically while "
    "spans verify clean, halves when a span contains a corrupt beacon",
    registry=CLIENT_REGISTRY)
CKPT_BOOTSTRAPS = Counter(
    "checkpoint_bootstraps_total",
    "Checkpoint trust bootstraps by result (ok = the signed checkpoint "
    "verified and the spot-check sample passed, head trust jumped in "
    "O(1); rejected = verification failed, fell back to the full walk)",
    ["result"], registry=CLIENT_REGISTRY)
CKPT_ISSUED = Counter(
    "checkpoint_issued_total",
    "Checkpoints recovered by the aggregator from piggybacked "
    "threshold partials at checkpoint-interval rounds",
    registry=GROUP_REGISTRY)
CKPT_ROUND = Gauge(
    "checkpoint_round",
    "Round of the latest recovered checkpoint served at "
    "/checkpoints/latest (0 until the first recovery)",
    registry=GROUP_REGISTRY)

# ---- OTLP export (obs/export.py) ------------------------------------------
OTLP_EXPORT_ROUNDS = Counter(
    "otlp_export_rounds_total",
    "Round traces handed to the OTLP exporter, by sink "
    "(http = POSTed to the collector, spool = appended to the on-disk "
    "NDJSON ring, dropped = both sinks failed)",
    ["sink"], registry=REGISTRY)


def batch_bucket(n: int) -> str:
    """Coarse batch-size bucket label — bounded cardinality for
    engine_op_seconds (matches the engine's compile-bucket scale)."""
    for b in (1, 8, 32, 128, 512):
        if n <= b:
            return str(b)
    return "512+"


def render() -> bytes:
    """The /metrics payload: all four registries gathered (the
    reference's chained-gatherer handler, metrics.go:229-266)."""
    return b"".join(generate_latest(r) for r in
                    (REGISTRY, GROUP_REGISTRY, HTTP_REGISTRY,
                     CLIENT_REGISTRY))
