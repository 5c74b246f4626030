"""Metric-name registry: every gauge/counter family in one place.

The InmemSink aggregates by exact name, so an unbounded set of names
(one per eval id, per node, per exception string...) grows the sink's
interval dicts without bound and makes ``/v1/metrics`` quadratic to
render. The ``metrics-discipline`` lint rule (nomad-lint) therefore
requires metric names at instrumentation sites to be dotted ``nomad.*``
string literals (or module constants), and requires every family —
``nomad.<family>`` — to be documented here. Dynamic names are allowed
through exactly one blessed door, :func:`publish_family`, which turns a
stats dict into per-key gauges under a registered family prefix; the
key set is bounded by construction (a stats dict's keys, a stage set),
never by workload identifiers.

Reference anchor: armon/go-metrics keeps names as compile-time label
slices (e.g. nomad/eval_broker.go:825 EmitStats); this registry is the
python-side equivalent of that greppable inventory.
"""
from __future__ import annotations

from typing import Dict, Mapping

from . import metrics

#: family prefix (``nomad.<family>``) -> what lives under it. The lint
#: rule's collect pass reads the literal keys of this dict; an
#: instrumentation site whose name falls outside every family fails the
#: tree gate until the family is documented here.
FAMILIES: Dict[str, str] = {
    "nomad.broker": "eval broker depths: total_ready/unacked/blocked, "
                    "dequeue_waiters (gauges, leader stats sweep)",
    "nomad.blocked_evals": "blocked-eval tracker: EmitStats depth gauges "
                           "(publish_family), unblock_batch_size/"
                           "unblock_to_place_ms samples, "
                           "unblock_deferred counter",
    "nomad.autoscaler": "leader autoscaler loop: blocked_depth/"
                        "nodes_added gauges, scale_up/scale_down "
                        "counters",
    "nomad.plan": "plan pipeline: queue_depth gauge; evaluate/apply/"
                  "wait_for_index samples; dense_nodes_rejected counter",
    "nomad.worker": "scheduler workers: dequeue_eval/async_handoff "
                    "counters; invoke_scheduler.<type>/wait_for_index "
                    "samples (<type> is the bounded eval-type enum)",
    "nomad.server": "server one-shots: first_job_latency_ms gauge",
    "nomad.sched": "scheduler internals: reconcile sample",
    "nomad.fsm": "state-machine apply counters: "
                 "dense_placements_committed",
    "nomad.device_batcher": "device dispatch batcher: stats gauges "
                            "(publish_family) + pad_stack/dispatch/"
                            "compute/transfer samples",
    "nomad.pipeline": "async eval-lifecycle pipeline: stats gauges "
                      "(publish_family) + acked/nacked/nack.<why>/"
                      "redispatch*/slots_exhausted/backpressure/... "
                      "counters",
    "nomad.tpu_engine": "placement kernel engine: handled/fallback/"
                        "encode_cache counters + "
                        "encode/apply/device_wait samples",
    "nomad.trace": "eval-lifecycle trace gauges: eval_ms percentiles, "
                   "inflight, slowest_inflight_ms, "
                   "pipeline.<stage>.* (publish_family)",
    "nomad.chaos": "chaos harness: failover.* probe gauges "
                   "(publish_family)",
    "nomad.watchdog": "liveness watchdog: fired/heartbeat counters, "
                      "stalled_s gauge",
    "nomad.heartbeat": "client heartbeat timers: active gauge",
    "nomad.state": "state store: latest_index gauge",
    "nomad.flight": "flight recorder self-telemetry: tick_ms sample, "
                    "frames/dropped counters, duty_cycle gauge",
    "nomad.rpc": "wire RPC layer: per-method latency_ms histograms, "
                 "req_bytes/resp_bytes samples, calls/errors/not_leader "
                 "counters (family_sample/family_counter — the method "
                 "enum is bounded by bind_server's registry), inflight "
                 "gauge",
    "nomad.watch": "blocking-query watch hub: watchers gauge, "
                   "wakeups/dropped_notifies/rejected_subscribes "
                   "counters",
}


def family_of(name: str) -> str:
    """``nomad.broker.total_ready`` -> ``nomad.broker``."""
    parts = name.split(".")
    return ".".join(parts[:2])


def publish_family(prefix: str, mapping: Mapping[str, object]) -> None:
    """Publish one gauge per numeric key of ``mapping`` under a
    registered family prefix — the single blessed site for dynamic
    metric names. Non-numeric values (notes, strings, bools ride along
    in stats dicts) are skipped, not coerced."""
    if family_of(prefix) not in FAMILIES:
        raise ValueError(
            f"metric family {prefix!r} is not registered in "
            f"nomad_tpu.utils.metric_names.FAMILIES"
        )
    for key, value in mapping.items():
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            continue
        metrics.set_gauge(f"{prefix}.{key}", float(value))


def _require_family(prefix: str) -> None:
    if family_of(prefix) not in FAMILIES:
        raise ValueError(
            f"metric family {prefix!r} is not registered in "
            f"nomad_tpu.utils.metric_names.FAMILIES"
        )


def family_sample(prefix: str, key: str, value: float) -> None:
    """Blessed dynamic-name door for SAMPLES (publish_family only does
    gauges): one histogram/summary series per ``<prefix>.<key>`` under a
    registered family. The key set must be bounded by construction — the
    RPC layer's per-method latency tables qualify (the method enum is
    the bind_server registry), per-eval or per-node keys do not."""
    _require_family(prefix)
    metrics.add_sample(f"{prefix}.{key}", value)


def family_counter(prefix: str, key: str, value: float = 1.0) -> None:
    """Blessed dynamic-name door for COUNTERS under a registered family
    (same bounded-key contract as :func:`family_sample`)."""
    _require_family(prefix)
    metrics.incr_counter(f"{prefix}.{key}", value)
