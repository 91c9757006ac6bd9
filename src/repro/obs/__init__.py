"""repro.obs — run-scoped telemetry across dispatch, execution, and bench.

One ``Telemetry`` per run collects counters/gauges/histograms, span and
instant events on the executor's clock, and per-kernel prediction-drift
status (live MAPE vs the fit-time band).  Every decision point in the
stack reports into it when one is attached — dispatch modes and gate
outcomes (``runtime.dispatch``), refits (``runtime.online``), steals,
queue depths (``exec.executor``), comm-model pricing
(``exec.comm``), and predicted-vs-realized makespans (``api.compile_``).
``exec.ExecutionTrace.to_chrome(telemetry=...)`` merges gauge series as
counter tracks and telemetry instants into the task timeline;
``python -m repro.obs report`` summarizes a saved telemetry file and
``--check`` gates on drift.  The program's own host spans go onto the
profiler's trace, on the device trace's clock (``trace_span``,
``trace_step``), and ``compile_counter`` counts JAX's compiles in the
process.

The second layer rides on the same document: the memory ledger
(``obs.memory``) accounts per-device live/peak bytes against the
compile-time predicted peak, model cards (``obs.cards``) fold tunecache
coverage with live accuracy per predictor, SLOs (``obs.slo``) price
latency objectives with burn rates, and ``obs.dashboard`` renders it all
as one self-contained static HTML file.

The third layer asks *why*: ``obs.explain`` reconstructs the dependency
DAG from an execution trace, computes the realized critical path and
per-task slack, partitions the makespan into compute/transfer/queue/
overhead buckets, diffs against the frozen EFT schedule's predicted
path, and ranks (kernel, shape-bucket) pairs by the makespan-seconds
their prediction error cost — plus per-request serve TTFT waterfalls
from the engine's trace-ID instants (``python -m repro.obs explain``).
"""
from repro.obs.cards import build_cards, format_cards
from repro.obs.dashboard import render_dashboard, write_dashboard
from repro.obs.drift import DriftConfig, DriftMonitor
from repro.obs.explain import (EXPLAIN_SCHEMA_VERSION, analyze_chrome,
                               analyze_trace, format_explain,
                               format_waterfalls, lane_utilization,
                               summarize_attribution,
                               waterfalls_from_telemetry)
from repro.obs.memory import (MemoryCapacityError, MemoryLedger, MemoryPlan,
                              check_capacity, memory_plan,
                              predicted_peak_bytes)
from repro.obs.report import format_summary
from repro.obs.slo import (DEFAULT_SERVE_SLOS, SLO, burned, evaluate_slos,
                           format_slos, load_slos)
from repro.obs.telemetry import (NULL_TELEMETRY, OBS_SCHEMA_VERSION,
                                 CompileCounter, NullTelemetry, Telemetry,
                                 as_telemetry, compile_counter,
                                 summarize_doc, trace_span, trace_step)
