"""Wave-level observability for the EDST stack.

Three pillars (see ``src/repro/dist/README.md`` -> "Observability"):

  * :mod:`repro.telemetry.metrics` -- process-wide counters / gauges /
    histograms with JSON and Prometheus-text export, fed by the
    executors, the health monitor, the recovery controller, the chaos
    injector and the train loop;
  * :mod:`repro.telemetry.trace`   -- Chrome-trace-event (Perfetto)
    export of any compiled wave program: spans per message, lanes per
    device or tree, flow events along the verifier's happens-before DAG,
    predicted (CostModel) timings, or per-wave times read from a device
    profile.

The third is the device profile itself: every executor runs each wave
under ``jax.named_scope("edst/t{tree}/w{wave}/{op}")`` and the train
step its phases under ``step/*`` and ``model/*`` scopes, so a profiler
trace of the compiled program attributes each op's time.

``metrics`` is pure stdlib and imported eagerly; ``trace`` needs NumPy
only and is loaded lazily.
"""
from __future__ import annotations

from . import metrics  # noqa: F401  (stdlib-only, always safe)

__all__ = ("metrics", "trace")


def __getattr__(name):
    if name == "trace":
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
