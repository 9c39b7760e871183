"""epoch_samples_per_s: the adaptive loop net of the host, samples in the
jobs' final flushes over their sampling seconds less the compile and
trace seconds that the sampling phase's ``phase.epoch`` and
``phase.flush`` annotations counted."""
from bench import phases


def read(run):
    jobs = run["jobs"]
    spans = phases.named(run, "phase.epoch", "phase.flush")
    if not jobs or not spans:
        return None
    host = sum(s["compile_s"] + s["trace_lower_s"] for s in spans)
    secs = sum(j["phase_seconds"]["sampling"] for j in jobs) - host
    if secs <= 0:
        return None
    return sum(j["tau"] for j in jobs) / secs
