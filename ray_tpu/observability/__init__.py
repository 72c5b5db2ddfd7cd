"""Observability: task timeline / profiling events.

Reference: ``python/ray/_private/profiling.py`` + the task-event pipeline
(``core_worker/task_event_buffer.h`` → ``gcs_server/gcs_task_manager.h``).
Redesign: a per-process lock-free-ish ring buffer of profile events
(``record_event``), aggregated on demand into a chrome://tracing JSON dump
(``dump_timeline``). Worker processes ship their buffers to the driver via
the controller KV on exit; in-process events are always available.
"""

from ray_tpu.observability.timeline import (
    PhaseClock,
    ProfileEvent,
    dump_timeline,
    record_event,
    timeline_events,
)

__all__ = [
    "PhaseClock",
    "ProfileEvent",
    "dump_timeline",
    "record_event",
    "timeline_events",
    "tracing",
]


def __getattr__(name: str):
    # lazy: tracing pulls in core.config/ids — load on first touch
    if name == "tracing":
        import importlib

        return importlib.import_module("ray_tpu.observability.tracing")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
