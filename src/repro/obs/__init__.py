"""repro.obs — unified metrics / tracing / profiling.

Layered as:

  metrics    process-wide registry: counters, gauges, labeled
             histograms (log-spaced buckets + exact-percentile
             reservoir); near-zero-cost NULL path when disabled
  trace      span tracker (context-manager + decorator), per-request
             lifecycle lanes, Chrome-trace/Perfetto JSON export; each
             span is also a jax.profiler annotation (``repro.<name>``)
  compiles   always-on count of the programs JAX compiles or loads
             from its persistent cache (``jit_programs()``)
  export     sinks: one-shot snapshot dict, Prometheus text
             exposition, JSONL event log, write_all artifact set
  jaxprof    scoped jax.profiler capture
  loadgen    seeded synthetic workloads (Poisson/gamma/bursty arrivals,
             mixed length dists, shared-prefix mixes, JSONL trace
             replay) + the open-loop virtual-time load driver
  slo        SLO spec + evaluation: attainment, goodput, sliding-window
             percentiles, queue-wait/prefill/decode decomposition

Metric names are stable and namespaced: ``repro_serving_*`` for the
runtime (TTFT/TPOT histograms, pool occupancy, spec accept rate,
JIT-cache hit/miss), ``repro_compress_*`` for the compression pipeline
(per-stage and per-shape-class timings), ``repro_plan_*`` for
progressive rounds, ``repro_jit_*`` for programs compiled or loaded
from the compile cache. ``benchmarks/bench_serving.py`` computes its SLO
percentiles from the same histograms the server reports — benchmark
numbers and production stats share one code path.
"""
from repro.obs import compiles, loadgen, slo
from repro.obs.compiles import jit_programs
from repro.obs.export import JsonlLog, snapshot, to_prometheus, write_all
from repro.obs.jaxprof import JaxProfiler
from repro.obs.loadgen import LengthDist, WorkloadSpec
from repro.obs.metrics import (
    DEFAULT_BUCKETS, NULL, Counter, Gauge, Histogram, Registry, counter,
    default_registry, disable, enable, enabled, gauge, histogram,
    log_buckets)
from repro.obs.slo import SLOMonitor, SLOSpec
from repro.obs.trace import (
    ENGINE_TRACK, NULL_CTX, NULL_TRACER, Tracer, request_track)

__all__ = [
    "Counter", "Gauge", "Histogram", "LengthDist", "Registry",
    "SLOMonitor", "SLOSpec", "Tracer", "JaxProfiler", "JsonlLog",
    "WorkloadSpec", "DEFAULT_BUCKETS", "ENGINE_TRACK", "NULL",
    "NULL_CTX", "NULL_TRACER", "counter", "default_registry",
    "disable", "enable", "enabled", "gauge",
    "histogram", "jit_programs", "loadgen", "log_buckets",
    "request_track", "slo", "snapshot", "to_prometheus", "write_all",
]

compiles.install()
