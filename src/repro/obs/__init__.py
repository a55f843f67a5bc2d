"""repro.obs — observability for the certification pipeline and serving.

The paper's pitch is *rigorous, a-priori* bounds; this package makes the
system that produces and serves them *observable*, in four pieces:

* :mod:`repro.obs.trace` — a lightweight span API with a JSONL event sink.
  ``obs.span("range_pass")`` / ``obs.counter("store.hits_mem")`` /
  ``obs.gauge(...)`` are module-level no-ops until a CLI installs a tracer
  (``--trace out.jsonl`` on ``python -m repro.certify``), after which one
  certify run yields a per-stage timing + ladder-compile-count + store
  hit/miss profile. While the JAX profiler collects, spans are also
  profiler host events on the device trace's clock (the serving engine's
  ``engine.*`` spans); ``obs.recording()`` says whether a span is kept.
* :mod:`repro.obs.metrics` — serving-side latency histograms
  (prefill/decode split), tokens/s and occupancy gauges, exported as JSONL
  and as a Prometheus text exposition (no server dependency).
* :mod:`repro.obs.monitors` — certificate-violation monitors: runtime
  numeric-health stats per scope (via
  :func:`repro.core.quantize.numeric_health` + ``jax.debug.callback``)
  compared against the certified IA enclosures and (δ̄, ε̄) bounds —
  overflow/underflow/saturation counters and per-scope "bound margin"
  gauges, so a certificate that under-covers live traffic is detected.
* :mod:`repro.obs.report` + the ``python -m repro.obs report`` CLI —
  renders a trace into per-stage/per-scope summary tables; ``validate``
  schema-checks a trace (the CI smoke gate). :mod:`repro.obs.bench`
  appends machine-readable ``BENCH_*.json`` entries so the perf
  trajectory accumulates across runs.

Instrumentation contract: library code imports ``from repro import obs``
and calls ``obs.span/counter/gauge/event`` freely — all are cheap no-ops
when no tracer is configured, so the analysis and serving hot paths pay
nothing by default, and nothing here ever changes a jitted value (monitor
stats leave jit through ``jax.debug.callback``).
"""
from .trace import (  # noqa: F401
    SCHEMA,
    Tracer,
    configure,
    counter,
    enabled,
    event,
    flush,
    gauge,
    get_tracer,
    load_events,
    recording,
    shutdown,
    span,
    validate_events,
)
from .log import get_logger  # noqa: F401
from .metrics import Histogram, MetricsRegistry  # noqa: F401
from .monitors import ViolationMonitor  # noqa: F401
from .bench import append_bench, check_regressions, read_bench  # noqa: F401


def __getattr__(name):
    # profile/costmodel are jax-adjacent (profile builds serving steps);
    # expose them lazily so `import repro.obs` stays as light as before
    if name in ("profile", "costmodel"):
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
