"""compile_s: host compile, the mean over the window's jobs of the
backend-compile seconds each ``run_adaptive`` call counted, net of
persistent-cache loads (its ``run.end`` annotation; the counter is
``repro.runtime.telemetry.host_counter_totals``)."""
from bench import phases


def read(run):
    ends = phases.named(run, "run.end")
    if not run["jobs"] or not ends:
        return None
    return sum(s["compile_s"] for s in ends) / len(ends)
