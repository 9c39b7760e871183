"""The program's own phases in a traced run.

While a profiler trace runs, ``run_adaptive`` leaves its spans on the
trace's host plane as annotations (``phase.diameter``,
``phase.calibration``, one ``phase.epoch`` per epoch, ``phase.flush``),
each closed with the compile and trace counters it spent as stats
(``compile_s``, ``compiles``, ``cache_load_s``, ``cache_hits``,
``trace_lower_s``, ``traces``), and one ``run.end`` instant per call with
the call's totals (``repro.runtime.telemetry``).  A program that leaves
none gives an empty list, and the metrics that read them nothing.
"""
from __future__ import annotations

from . import harness, tracing

NAMES = ("phase.diameter", "phase.calibration", "phase.epoch",
         "phase.flush", "run.end")


def read(path: str) -> list:
    """[(start_ns, end_ns, name, stats)] of the program's annotations
    inside the ``bench.window`` annotation of one ``.xplane.pb``, in
    time order."""
    from jax.profiler import ProfileData
    found, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tracing.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in NAMES:
                    found.append((float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns),
                                  ev.name, dict(ev.stats)))
    if window is None:
        raise ValueError(f"no {tracing.WINDOW!r} annotation in {path}")
    return sorted(a for a in found
                  if window[0] <= a[0] and a[1] <= window[1])


def annotations(run: dict):
    """The window's program annotations (:func:`read`), read from the
    run's trace once and kept in ``run["phases"]``; ``None`` for an
    untraced run."""
    if run.get("trace") is None:
        return None
    if "phases" not in run:
        run["phases"] = read(tracing.find_xplane(str(harness.TRACE_DIR)))
    return run["phases"]


def named(run: dict, *names: str) -> list:
    """The stats of the window's annotations with one of ``names``."""
    return [stats for _, _, name, stats in annotations(run) or ()
            if name in names]
