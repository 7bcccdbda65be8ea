"""``horovod_tpu.metrics`` — engine-to-endpoint telemetry.

The live observability plane for horovod_tpu (SURVEY §5.5): a
dependency-free metric registry fed by

- the **C++ engine stats bridge** — ``hvt_engine_stats()`` atomics
  (cycles, coordinated tensors, cache hits/misses, fusion bytes, fused
  responses, stalls, per-op execution time) polled at scrape time via
  ``common/basics.py:poll_engine_stats``;
- the **eager collective instrumentation** — per-(op, process-set)
  latency histograms and byte counters around every eager dispatch
  (``ops/collective_ops.py``);
- the **elastic driver** — alive hosts, blacklist size, rendezvous
  rounds (``runner/elastic/driver.py``).

Consumption paths:

- ``GET /metrics`` on the elastic rendezvous server
  (``runner/http_server.py``) or the standalone :func:`serve` endpoint
  (``hvtrun --metrics-port`` starts it per worker);
- :func:`json_snapshot`, for a record that must carry its counters
  where the live endpoint is unreachable;
- ``MetricsCallback`` (``hvt.jax.callbacks`` / ``hvt.keras``) folding
  training-loop metrics into the registry.

Fleet-scale surfaces (PR 13):

- :mod:`horovod_tpu.metrics.merge` — the associative snapshot-merge
  algebra (counters summed, gauges maxed, histogram buckets added)
  per-host telemetry leaders fold member snapshots with;
- :mod:`horovod_tpu.metrics.telemetry` — the leader-aggregated push
  plane, the ``/statusz`` gang rollup, and the health-rule engine
  behind ``hvt_health_alerts_total`` (live monitor: ``python -m
  horovod_tpu.tools.hvt_top``).

Typical use::

    from horovod_tpu import metrics
    port = metrics.serve(9090)          # or hvtrun --metrics-port 9090
    metrics.counter("my_steps_total", "steps run").inc()
    print(metrics.prometheus_text())
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

from horovod_tpu.metrics.registry import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS, Metric, MetricError, MetricRegistry)
from horovod_tpu.metrics import exposition as _exposition
from horovod_tpu.metrics.exposition import (  # noqa: F401
    PROMETHEUS_CONTENT_TYPE, MetricsServer)

# reentrant: serve() resolves registry() while holding it
_lock = threading.RLock()
_registry: Optional[MetricRegistry] = None
_server: Optional[MetricsServer] = None


def registry() -> MetricRegistry:
    """The process-wide default registry. Created on first use with the
    engine stats collector installed, so every scrape/snapshot carries
    fresh ``hvt_engine_*`` counters (zeros when the engine is absent —
    the series must exist either way so dashboards don't go blank), and
    with the collector of this process's start (``metrics/startup.py``:
    ``hvt_startup_seconds``, ``hvt_jax_stage_*``)."""
    global _registry
    with _lock:
        if _registry is None:
            _registry = MetricRegistry()

            def _engine_collector():
                # late import: basics ↔ metrics would cycle at module load
                from horovod_tpu.common import basics

                basics.poll_engine_stats(_registry)

            def _startup_collector():
                from horovod_tpu.metrics import startup

                startup.collect(_registry)

            _registry.register_collector(_engine_collector)
            _registry.register_collector(_startup_collector)
        return _registry


# ---------------------------------------------------------------- factories
def counter(name: str, help: str = "",
            labelnames: Sequence[str] = ()) -> Metric:
    return registry().counter(name, help, labelnames)


def gauge(name: str, help: str = "",
          labelnames: Sequence[str] = ()) -> Metric:
    return registry().gauge(name, help, labelnames)


def histogram(name: str, help: str = "", labelnames: Sequence[str] = (),
              buckets: Optional[Sequence[float]] = None) -> Metric:
    return registry().histogram(name, help, labelnames, buckets=buckets)


# ------------------------------------------------------------ serialization
def prometheus_text(reg: Optional[MetricRegistry] = None) -> str:
    return _exposition.prometheus_text(reg or registry())


def json_snapshot(reg: Optional[MetricRegistry] = None) -> dict:
    return _exposition.json_snapshot(reg or registry())


# ------------------------------------------------------------------ serving
def serve(port: int = 0, addr: str = "0.0.0.0") -> int:
    """Start (or return) the process-wide scrape endpoint; returns the
    bound port. Idempotent — a second call returns the running server's
    port. ``hvtrun --metrics-port`` calls this from ``hvt.init()`` with
    ``port + process_rank`` so co-hosted workers don't collide."""
    global _server
    with _lock:
        if _server is None:
            _server = MetricsServer(registry())
            _server.start(port=port, addr=addr)
        return _server.port


def server_port() -> Optional[int]:
    with _lock:
        return _server.port if _server is not None else None


def stop_server():
    global _server
    with _lock:
        if _server is not None:
            _server.stop()
            _server = None


def reset():
    """Drop the default registry and endpoint (tests only)."""
    global _registry, _server
    with _lock:
        if _server is not None:
            _server.stop()
            _server = None
        _registry = None
