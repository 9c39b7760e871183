"""job_s: wall time of the jobs completed in the window over their number
(host clock, each job timed from the call to the scores on the host)."""


def read(run):
    jobs = run["jobs"]
    if not jobs:
        return None
    return sum(j["seconds"] for j in jobs) / len(jobs)
