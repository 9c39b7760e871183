"""level_ms: the adaptive loop's cost per BFS level, device milliseconds
of the epoch program (``jit_epoch_step``) in the traced window over the
BFS expansions its epochs ran (the ``bfs_levels`` stats of the window's
``phase.epoch`` annotations; ``repro.core.engine.run_adaptive``)."""
from bench import phases, roofline


def read(run):
    epochs = phases.named(run, "phase.epoch")
    if not epochs or any("bfs_levels" not in s for s in epochs):
        return None
    levels = sum(s["bfs_levels"] for s in epochs)
    if levels <= 0:
        return None
    return 1e3 * roofline.epoch_device_s(run["trace"]) / levels
