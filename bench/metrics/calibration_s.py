"""calibration_s: calibration (``core/engine.py`` phase 2: the
calibration draws and the stop-rule parameters), the mean of the jobs'
``phase_seconds["calibration"]``; the phase ends on a blocking read of
both, so the span holds its device time."""


def read(run):
    jobs = run["jobs"]
    if not jobs:
        return None
    return sum(j["phase_seconds"]["calibration"] for j in jobs) / len(jobs)
