"""setup_s: everything before the window (host clock): building the graph,
putting it on the device, and the warm-up job that compiles (or loads
from the persistent cache) every program the window runs."""


def read(run):
    return run["setup_s"]
