"""epoch_idle_share: the device idle inside the adaptive epochs, in %:
the device's idle time inside the window's ``phase.epoch`` annotations
less the compile and trace seconds those epochs counted, over the
epochs' time less the same (mean over the cell's devices)."""
from bench import phases


def _overlap(gaps, spans):
    return sum(max(0.0, min(e, se) - max(s, ss))
               for s, e in gaps for ss, se in spans)


def read(run):
    anns = phases.annotations(run)
    epochs = [(s, e, st) for s, e, name, st in anns or ()
              if name == "phase.epoch"]
    if not epochs:
        return None
    spans = [(s, e) for s, e, _ in epochs]
    host_ns = 1e9 * sum(st["compile_s"] + st["trace_lower_s"]
                        for _, _, st in epochs)
    span_ns = sum(e - s for s, e in spans) - host_ns
    if span_ns <= 0:
        return None
    devices = run["trace"].devices
    idle_ns = sum(_overlap(d.gaps, spans) for d in devices) / len(devices)
    return 100.0 * (idle_ns - host_ns) / span_ns
