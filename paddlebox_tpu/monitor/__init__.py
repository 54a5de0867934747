"""paddlebox_tpu.monitor — the unified telemetry hub.

One API over the observability primitives the reference ships separately
(StatRegistry counters, log_for_profile stage lines, chrome-trace
timelines, dump threads): tagged events/spans with pass/step context that
worker threads inherit, pluggable sinks, per-pass flight records, and
Prometheus-style exposition. See ``docs/PARITY.md`` "Telemetry hub".

Import order note: this package imports NOTHING from ``paddlebox_tpu.utils``
— ``utils.profiler``/``utils.timer`` import *us* (and re-export shims), so
the dependency points one way.
"""

from paddlebox_tpu.monitor import context  # noqa: F401
from paddlebox_tpu.monitor.registry import STATS, StatRegistry  # noqa: F401
from paddlebox_tpu.monitor.sinks import (JsonlSink, MemorySink,  # noqa: F401
                                         ParityLogSink, Sink)
from paddlebox_tpu.monitor.flight import (  # noqa: F401
    EVENT_REQUIRED_KEYS, FLIGHT_REQUIRED_FIELDS, validate_event,
    validate_events_file, validate_flight_record)
from paddlebox_tpu.monitor.hub import (TelemetryHub, counter_add,  # noqa: F401
                                       event, gauge_set, hub, span,
                                       start_metrics_endpoint)
from paddlebox_tpu.monitor.timers import StageTimers  # noqa: F401
from paddlebox_tpu.monitor.device_scopes import device_scope  # noqa: F401
