"""diameter_s: phase 1 (``core/diameter.py``), the mean of the jobs'
``phase_seconds["diameter"]``; phase 1 ends on a blocking read of the
bound, so the span holds its device time."""


def read(run):
    jobs = run["jobs"]
    if not jobs:
        return None
    return sum(j["phase_seconds"]["diameter"] for j in jobs) / len(jobs)
