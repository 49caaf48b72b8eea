"""``--trace-out``/``--metrics-out`` capture for the command-line tools.

:class:`ObsCapture` installs a ``repro.obs`` tracer and/or metrics
registry for the duration of a command, then writes the artifacts on the
way out (:meth:`ObsCapture.finish`).  Shared by the configurator's CLI
(search and the replay family) and the serve entry point
(``python -m repro.launch.serve``).
"""
from __future__ import annotations

import json
import sys
from typing import Callable, Optional

from repro.obs.flight import configure_flight_recorder, flight_config
from repro.obs.metrics import disable_metrics, enable_metrics
from repro.obs.trace import Tracer, disable_tracing, enable_tracing

__all__ = ["ObsCapture"]


def _write_stdout(text: str) -> None:
    sys.stdout.write(text)
    sys.stdout.flush()


class ObsCapture:
    """``--trace-out``/``--metrics-out``: install a ``repro.obs`` tracer
    and/or metrics registry for the duration of the command, then write
    the artifacts on the way out (``finish``).  The trace artifact keeps
    wall times out, so seeded runs write byte-identical files;
    ``--trace-out -`` streams the JSONL to stdout (through ``emit_text``),
    a ``.chrome.json`` suffix writes the Chrome ``trace_event`` export
    instead (load it in chrome://tracing or Perfetto).  The
    flight-recorder sampling knobs (``--span-sample-every``/
    ``--max-request-spans``) bound per-request span volume on big traces.

    ``tracer`` is the tracer to install, for a command that needs one
    of its own (the serve entry point's wall-timebase, profiler-bridged
    tracer); it is installed even without ``--trace-out``, and its
    artifact is written only with it."""

    def __init__(self, args, command: Optional[str] = None,
                 tracer: Optional[Tracer] = None,
                 emit_text: Callable[[str], object] = _write_stdout):
        self.trace_out = getattr(args, "trace_out", "")
        self.metrics_out = getattr(args, "metrics_out", "")
        self.meta = {"command": command or getattr(args, "command", None)
                     or "search",
                     "model": getattr(args, "model", None)}
        self.tracer = self.registry = None
        self._emit_text = emit_text
        self._flight_restore = None
        sample = getattr(args, "span_sample_every", None)
        cap = getattr(args, "max_request_spans", None)
        if sample is not None or cap is not None:
            prev = flight_config()
            self._flight_restore = (prev.sample_every,
                                    prev.max_request_spans)
            configure_flight_recorder(
                sample_every=sample if sample is not None else 1,
                max_request_spans=cap if cap is not None else 512)
        if self.trace_out or tracer is not None:
            self.tracer = enable_tracing(tracer)
        if self.metrics_out:
            self.registry = enable_metrics()

    def finish(self) -> None:
        if self._flight_restore is not None:
            configure_flight_recorder(*self._flight_restore)
        if self.tracer is not None:
            disable_tracing()
            if self.trace_out:
                self._write_trace()
        if self.registry is not None:
            disable_metrics()
            if self.metrics_out.endswith(".prom"):
                text = self.registry.to_prometheus()
            else:
                text = json.dumps(self.registry.to_dict(), indent=2,
                                  sort_keys=True) + "\n"
            with open(self.metrics_out, "w") as f:
                f.write(text)

    def _write_trace(self) -> None:
        art = self.tracer.artifact(meta=self.meta)
        if self.trace_out == "-":
            self._emit_text(art.to_jsonl())
        elif self.trace_out.endswith(".chrome.json"):
            with open(self.trace_out, "w") as f:
                f.write(json.dumps(art.to_chrome_trace(),
                                   sort_keys=True) + "\n")
        else:
            art.save(self.trace_out)
