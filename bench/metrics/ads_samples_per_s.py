"""ads_samples_per_s: the adaptive loop (``core/engine.py`` epochs,
``core/sampler.py``, ``core/bfs.py``): samples in the jobs' final flushes
over their calibration and sampling seconds.  Calibration is in the
denominator because its device time runs on into the first epoch (its
span ends on a dispatch, not a blocking read)."""


def read(run):
    jobs = run["jobs"]
    secs = sum(j["phase_seconds"]["calibration"]
               + j["phase_seconds"]["sampling"] for j in jobs)
    if not jobs or secs <= 0:
        return None
    return sum(j["tau"] for j in jobs) / secs
