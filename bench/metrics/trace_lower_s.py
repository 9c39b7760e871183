"""trace_lower_s: host tracing and lowering per call, the mean over the
window's jobs of the seconds each ``run_adaptive`` call spent tracing
jaxprs and lowering them to MLIR (its ``run.end`` annotation; the
counter is ``repro.runtime.telemetry.host_counter_totals``)."""
from bench import phases


def read(run):
    ends = phases.named(run, "run.end")
    if not run["jobs"] or not ends:
        return None
    return sum(s["trace_lower_s"] for s in ends) / len(ends)
