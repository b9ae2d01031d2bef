"""Trace subsystem: record where a run's time goes, replay it under what-ifs.

  events.py   TraceRecorder — structured spans (local_step / ef_encode /
              collective / ckpt / eval) on one perf_counter clock, with
              modeled device/wire costs attached from roofline + comm;
              HostSpans — the train loop's ``train.*`` spans on the
              profiler's clock, which give the recorder its boundaries;
  chrome.py   lossless Chrome trace_event export (Perfetto: workers as
              rows, sync rounds as flow arrows);
  scopes.py   the step programs' named scopes (``jax.named_scope``) and
              ``op_scopes``, which maps each compiled instruction to one;
  replay.py   trace-driven what-if engine — re-simulate the recorded
              critical path under substituted fabric / workers / H /
              threshold / codec / collective-count knobs, and the CI gate
              that pins predicted-vs-measured wall and sync schedule.
"""
from repro.trace.events import (SCHEMA_VERSION, SPAN_KINDS, Span, Trace,
                                TraceRecorder)

#: chrome/replay are ALSO `python -m` entrypoints — importing them eagerly
#: here would re-execute them under runpy (RuntimeWarning), so they load
#: lazily on attribute access. The `replay` FUNCTION is deliberately not
#: re-exported here: importing the submodule binds the package attribute
#: `repro.trace.replay` to the MODULE, so a same-named function alias would
#: silently change type after the first access — use
#: ``repro.trace.replay.replay`` (or import from the submodule).
_LAZY = {
    "from_chrome": "chrome", "to_chrome": "chrome",
    "DEFAULT_TOL": "replay", "REPLAY_CODECS": "replay",
    "ReplayKnobs": "replay", "ReplayResult": "replay",
    "sweep_H": "replay", "sweep_codecs": "replay",
    "sweep_workers": "replay", "validate": "replay",
}

__all__ = ["SCHEMA_VERSION", "SPAN_KINDS", "Span", "Trace", "TraceRecorder",
           *sorted(_LAZY)]


def __getattr__(name: str):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f"repro.trace.{_LAZY[name]}")
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
