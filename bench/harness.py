"""The benchmark harness: one cell of ``BENCHMARK.json``, run end to end.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name that ``BENCHMARK.json`` gives it:

* ``bench/configs/<file>.json`` (the cell's ``config`` entry names the
  file): the deployment's graph generator and its parameters, the job's
  settings, its stated guarantees, its control and the limits of the
  comparison;
* ``bench/generators/<name>.py``: ``edges(params, seed)``;
* ``bench/traffic/<name>.json``: the job mix (metrics, draw stream,
  lane);
* ``bench/metrics/<name>.py``: ``read(run)`` returning the metric's value
  or ``None`` where the run holds nothing to read.

The window drives ``repro.core.run_adaptive`` through its public
signature: one job is one call, from a graph already on the device to
the scores on the host, run in a closed loop one job at a time, each at
a key of its own drawn from the seed and the job's index.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import time

import numpy as np

from . import check, reference, tracing

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(spec: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _load_json(ROOT / conf["file"])
    traffic = _load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def job_key(seed: int, index: int) -> np.ndarray:
    """Raw threefry key (uint32[2]) of job ``index`` of the run under
    ``seed``: index 0 is the warm-up job, 1, 2, ... the window's, so no
    two jobs of a run draw the same samples.

    The program's epoch step holds the calibration's stop-rule
    parameters as compile-time constants, so each new key compiles one
    new epoch program inside its job, as it does for a user's call."""
    return np.random.SeedSequence([seed % (1 << 64), 1, index]) \
        .generate_state(2, np.uint32)


class CompileCounter:
    """Counts JAX's compile-path events (backend compiles and their
    seconds, persistent cache hits, jaxpr traces) while ``active``."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile",
              "/jax/core/compile/jaxpr_trace_duration": "trace"}

    def __init__(self):
        import jax
        self.active = False
        self.counts = {"compile": 0, "trace": 0, "cache_hit": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1
            if self.EVENTS[event] == "compile":
                self.compile_s += duration

    def _event(self, event, **_):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hit"] += 1

    def backend_compiles(self) -> int:
        # a persistent-cache hit passes through the compile event too
        return self.counts["compile"] - self.counts["cache_hit"]


def configure_jax():
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a size cap from the environment evicts the window's
    # programs before the window runs
    jax.config.update("jax_compilation_cache_max_size", -1)


@contextlib.contextmanager
def no_cache_writes():
    """Programs compiled inside the block are read from the persistent
    cache but not written to it: a job's per-key epoch program then
    compiles in every run, as a user's call at a new key does, also
    where a later run repeats the seed."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chip_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def build_graph(config: dict):
    """The deployment's edge list from its generator (host), and the
    vertex count."""
    gen = load_module("generators", config["generator"])
    params = config["graph"]
    return gen.edges(params, int(params.get("seed", 0)))


def make_job(config: dict, traffic: dict, edges, n, device):
    """Put the graph on the device and return ``job(key) -> dict``."""
    import jax
    from repro.core import AdaptiveConfig, run_adaptive
    from repro.core.graph import from_edge_list

    if traffic["lane"] != "single":
        raise ValueError(f"unknown lane {traffic['lane']!r}")
    g = jax.device_put(from_edge_list(edges, n), device)
    jax.block_until_ready(g)
    a = config["adaptive"]
    acfg = AdaptiveConfig(eps=float(a["eps"]), delta=float(a["delta"]),
                          n0_base=int(a["n0_base"]),
                          n0_exponent=float(a["n0_exponent"]),
                          max_epochs=int(a["max_epochs"]),
                          sample_batch_size=a.get("sample_batch_size"))
    metrics = tuple(traffic["metrics"])

    def job(key) -> dict:
        res = run_adaptive(g, metrics, key=key, config=acfg,
                           stream=traffic["stream"])
        rep = res.reports[0]
        return {"scores": np.asarray(rep.scores), "tau": int(res.tau),
                "n_epochs": int(res.n_epochs),
                "vertex_diameter": int(res.vertex_diameter),
                "batch_size": int(res.batch_size), "route": res.route,
                "phase_seconds": dict(res.phase_seconds)}

    return job, g


def expected(config: dict, edges, n, seed):
    """The reference's side of the comparison (host only)."""
    rg = reference.build(edges, n)
    rng = np.random.default_rng([seed % (1 << 64), 7])
    moments, best = reference.sampled_pair_moments(
        rg, rng, int(config["reference"]["bfs_sources"]))
    a = config["adaptive"]
    epochs, n0 = int(a["max_epochs"]), int(a["n0_base"])
    return check.Expected(
        n=n, deg=rg.deg, vd_lower=reference.vertex_diameter_lower(rg, best),
        pair_mean=moments.mean, pair_var=moments.var, pair_se=moments.se,
        samples_lo=epochs * n0, samples_slack=epochs)


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, log=print) -> dict:
    """Set up, warm up, measure, compare; returns the result line."""
    import jax
    counter = CompileCounter()
    t_setup = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.setup"):
        edges, n = build_graph(cell.config)
        job, graph = make_job(cell.config, cell.traffic, edges, n,
                              devices[0])
    with jax.profiler.TraceAnnotation("bench.warmup"):
        warm = job(job_key(seed, 0))
    setup_s = time.perf_counter() - t_setup
    log(f"setup: {setup_s:.3f} s (graph n={n}, route {warm['route']}, "
        f"B={warm['batch_size']}, tau {warm['tau']}, warm job "
        f"{warm['phase_seconds']})")

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    jobs, errors = [], []
    counter.active = True
    t0 = time.perf_counter()
    with no_cache_writes(), jax.profiler.TraceAnnotation(tracing.WINDOW):
        while True:
            left = seconds - (time.perf_counter() - t0)
            if jobs and jobs[-1]["seconds"] > left:
                break
            idx = len(jobs) + 1
            with jax.profiler.TraceAnnotation(f"bench.job.{idx}"):
                tj = time.perf_counter()
                try:
                    out = job(job_key(seed, idx))
                except Exception as e:  # noqa: BLE001 - a failed job
                    errors.append(f"job {idx}: {type(e).__name__}: {e}")
                    break
                out["seconds"] = time.perf_counter() - tj
            jobs.append(out)
    window_s = time.perf_counter() - t0
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    log(f"window: {window_s:.3f} s, {len(jobs)} jobs "
        f"{[round(j['seconds'], 4) for j in jobs]}")
    compiles = {"backend": counter.backend_compiles(),
                "seconds": counter.compile_s,
                "cache_hits": counter.counts["cache_hit"],
                "traces": counter.counts["trace"]}
    log(f"compiles in window: {compiles['backend']} backend compiles "
        f"({compiles['seconds']:.3f} s with the cache hits' loads; "
        f"persistent-cache hits {compiles['cache_hits']}, jaxpr traces "
        f"{compiles['traces']})")
    dev = device_info(devices)

    # the reference runs once the window has closed and the program's
    # graph is freed
    del graph, job
    exp = expected(cell.config, edges, n, seed)
    per_job = [check.compare(j, exp) for j in jobs]
    table, ok = check.judge(per_job, cell.config["limits"])
    ok = ok and not errors
    failed = sum(1 for j in per_job
                 if any(j[k] > cell.config["limits"][k] for k in j))
    failed += len(errors)

    run = {"jobs": jobs, "setup_s": setup_s, "window_s": window_s,
           "trace": None}
    result = {"correct": ok, "attempted": len(jobs) + len(errors),
              "failed": failed}
    if trace:
        summary = tracing.summarize(tracing.find_xplane(str(TRACE_DIR)))
        run["trace"] = summary
        dev["busy_s"] = summary.busy_s()
        dev["window_s"] = summary.window_s()
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    values = {}
    for m in metrics:
        v = load_module("metrics", m["name"]).read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = values
    result["device"] = dev
    result["window_compiles"] = compiles
    if trace:
        result["breakdown"] = run["trace"].breakdown()
    for e in errors:
        log(f"error: {e}")
    for k, row in table.items():
        log(f"check {k}: {row['value']} (limit {row['limit']})")
    result["checks"] = table
    return result
