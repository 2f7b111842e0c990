"""repro.obs -- the observability subsystem (DESIGN.md §Obs).

Three layers, threaded through the engine / async / scale / comm / kernels
stacks without touching their math:

* :mod:`repro.obs.bus`    -- the in-jit telemetry bus: a typed
  :class:`Telemetry` pytree of optimizer-health counters riding the round
  metrics (``RoundMetrics.telemetry``), gated by
  :class:`repro.configs.base.ObsConfig` -- disabled is bit-for-bit the
  un-instrumented engine.
* :mod:`repro.obs.trace`  -- stage-level tracing: ``jax.named_scope``
  scopes on the round stages, the model's blocks, the local updates, the
  wire's data movement and the Pallas call sites, so every device op of a
  profile names its layer, plus :class:`ProfileWindow` (the launcher's
  ``--profile start:stop`` Perfetto capture).
* :mod:`repro.obs.sinks`  -- the :class:`MetricsSink` registry (memory /
  jsonl / stdout) every launcher reports through; :mod:`repro.obs.log` is
  the leveled stdout logger behind the launchers' ``--log-level``.
"""
from repro.obs.bus import (Telemetry, empty_telemetry,  # noqa: F401
                           residual_norm, ring_init, round_telemetry,
                           staleness_hist, window_wrap)
# NB: the `log` *function* is not re-exported at package level -- it would
# shadow the `repro.obs.log` submodule attribute and break
# `from repro.obs import log as obs_log` in the launchers.
from repro.obs.log import get_level, set_level  # noqa: F401
from repro.obs.sinks import (MetricsSink, get_sink, register_sink,  # noqa: F401
                             rows, sink_names)
from repro.obs.trace import ProfileWindow, stage  # noqa: F401

__all__ = [
    "Telemetry", "empty_telemetry", "residual_norm", "ring_init",
    "round_telemetry", "staleness_hist", "window_wrap",
    "MetricsSink", "get_sink", "register_sink", "rows", "sink_names",
    "ProfileWindow", "stage", "set_level", "get_level",
]
