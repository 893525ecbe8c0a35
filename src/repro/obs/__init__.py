"""obs — round-lifecycle tracing + metrics for the federation stack.

Dependency-free observability in three pieces:

* :mod:`repro.obs.tracer` — host-side spans (``perf_counter_ns``,
  thread-aware) nested with ``jax.profiler.TraceAnnotation`` device
  annotations, exported as Chrome trace-event JSON (Perfetto-loadable) and a
  JSONL event stream.
* :mod:`repro.obs.metrics` — typed counters / gauges / histograms behind a
  get-or-create registry.
* :mod:`repro.obs.recorder` — the facade every layer records through:
  ``make_recorder("off")`` returns the shared zero-overhead :data:`NULL`
  no-op, ``"basic"`` collects metrics + per-round records, ``"trace"`` adds
  spans. The per-round record is the unit ``scripts/obs_report.py``
  summarizes: close latency split into dispatch vs block-until-ready, ring
  occupancy/evictions/stale drops, sampled/straggler/dropout/delivered
  client counts, ledger bytes reconciled against core/comm.py, resolved
  divergence, compile-cache hits/misses.

Instrumented layers: fedsrv/coordinator.py (round open → uplinks →
quorum/deadline → close → downlink as nested spans, async commit/staleness
events), core/engine.py (close dispatch; DeferredDivergence resolution as
its own span), engine.RoundBuffers (begin/write/take/evict),
fedsrv/transport.py (encode/decode byte counts), core/federated.py +
launch/mesh_train.py (trainer round loop). Wired up via
``FedConfig.obs = off|basic|trace`` and the launcher's ``--obs`` /
``--trace`` / ``--metrics-out`` flags.

The overlap invariant this layer proves from span timestamps (the host-side
counterpart of ROADMAP's TPU-profile item): round N+1 ``ring.write`` span
intervals intersect round N's close window [``close.dispatch`` start,
``divergence.resolve`` end] — the ring genuinely streams the next round's
uplinks while the previous close is in flight.
"""

from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.recorder import (NULL, NULL_SPAN, OBS_MODES, NullRecorder,
                                Recorder, make_recorder)
from repro.obs.tracer import Span, Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL",
    "NULL_SPAN",
    "NullRecorder",
    "OBS_MODES",
    "Recorder",
    "Span",
    "Tracer",
    "make_recorder",
]
