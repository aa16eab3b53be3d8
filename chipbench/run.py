"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the run needs is found by name: the cell's configuration file
(``configs[].file``), its traffic mix ``chipbench/traffic/<traffic>.json``,
the driver the mix names ``chipbench/traffic/<driver>.py``, and each
per-layer metric's reader ``chipbench/metrics/<metric>.py``.

A run: check for the TPU (exit non-zero, no result line, without one),
generate the history from ``--seed``, build the program's store and warm
up every launch shape (all of that is ``setup_s``), measure for
``--seconds``, drain, read the device's peak memory, release the program's
state, and compare a seeded sample of the answers with the plain reference
(``reference.py``). With ``--trace 1`` the window runs under the profiler
and the result carries the per-layer metrics instead of the end-to-end
ones. The last line of standard output is one JSON object.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import graph500  # noqa: E402
import reference  # noqa: E402

#: Fixed cache and trace locations inside the checkout (gitignored).
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"
#: Store methods whose outermost calls count as host preparation.
PREP_METHODS = ("window_keys", "delta_keys", "block_for_keys", "delta_stack",
                "slide_stack")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_file_module(path: pathlib.Path):
    """Import one benchmark file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: ``trace.py`` by path: the standard library has a module of that name.
tracemod = load_file_module(HERE / "trace.py")


class CompileCounter:
    """Counts XLA executables the process obtains (compiled or cache-loaded)."""

    def __init__(self):
        self.total = 0
        self.cache_hits = 0
        self.names: "list[str]" = []

    def install(self) -> None:
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.total += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


@dataclasses.dataclass
class Answer:
    """One answer handed to a client: the window (a snapshot is (s, s))."""

    window: "tuple[int, int]"
    alg: str
    source: int
    values: object          # device array until the window has closed


@dataclasses.dataclass
class Run:
    """What one run hands its driver and its per-layer metric readers."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    history: object = None          # graph500.History
    store: object = None
    state: object = None            # the driver's own objects
    compiles: CompileCounter = dataclasses.field(default_factory=CompileCounter)
    prep_s: float = 0.0             # host preparation seconds, whole run
    prep_depth: int = 0
    setup_s: float = 0.0
    window_open_t: float = 0.0
    window_close_t: float = 0.0
    window_compiles: int = 0
    window_prep_s: float = 0.0
    e2e: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    unanswered: int = 0
    answers: "list[Answer]" = dataclasses.field(default_factory=list)
    trace_data: object = None
    trace_lib = tracemod            # trace reduction, for the metric readers
    _window_span: object = None
    _compiles_at_open: int = 0
    _prep_at_open: float = 0.0

    def rng(self, stream: int):
        """An independent numpy generator for one use of the seed."""
        import numpy as np
        return np.random.default_rng([self.seed, stream])

    @staticmethod
    def log(msg: str) -> None:
        log(msg)

    def answer(self, window, alg: str, source: int, values) -> None:
        """Record one answer handed to a client."""
        self.answers.append(Answer(tuple(window), alg, int(source), values))

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op without ``--trace 1``)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def open_window(self) -> float:
        """End set-up and start the measured window; returns its start time."""
        if self.trace:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0    # host spans, no Python calls
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
            self._window_span = jax.profiler.TraceAnnotation(
                tracemod.WINDOW_SPAN)
            self._window_span.__enter__()
        self._compiles_at_open = self.compiles.total
        self._prep_at_open = self.prep_s
        self.window_open_t = time.perf_counter()
        self.setup_s = self.window_open_t - T_PROCESS
        return self.window_open_t

    def close_window(self) -> float:
        """End the measured window; returns its length in seconds."""
        self.window_close_t = time.perf_counter()
        self.window_compiles = self.compiles.total - self._compiles_at_open
        self.window_prep_s = self.prep_s - self._prep_at_open
        if self.trace:
            import jax
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return self.window_close_t - self.window_open_t


def timed_store(run: Run, seq):
    """The program's ``SnapshotStore``, its host preparation timed.

    The outermost call of each method in ``PREP_METHODS`` adds its host
    seconds to ``run.prep_s`` and, when tracing, opens the span ``cb.store``.
    A method the store no longer has stops the run: the host preparation
    it stood for would otherwise drop out of the reading unseen.
    """
    from repro.core.snapshots import SnapshotStore

    missing = [m for m in PREP_METHODS if not hasattr(SnapshotStore, m)]
    if missing:
        raise RuntimeError(f"SnapshotStore has no {', '.join(missing)}: the "
                           "benchmark cannot time its host preparation")

    def timed(fn):
        def wrapper(self, *args, **kwargs):
            if run.prep_depth:
                return fn(self, *args, **kwargs)
            run.prep_depth += 1
            t0 = time.perf_counter()
            try:
                with run.span("cb.store"):
                    return fn(self, *args, **kwargs)
            finally:
                run.prep_s += time.perf_counter() - t0
                run.prep_depth -= 1
        return wrapper

    methods = {m: timed(getattr(SnapshotStore, m)) for m in PREP_METHODS}
    return type("TimedStore", (SnapshotStore,), methods)(seq)


def build_history(run: Run) -> None:
    """Generate the configuration's history from the seed; build the store."""
    from repro.graph.generators import EvolvingSequence

    g = run.config
    t0 = time.perf_counter()
    run.history = h = graph500.History(g, run.seed)

    class Sequence(EvolvingSequence):
        """The generated history; its weights come from the benchmark."""

        def weights_for(self, keys):
            return h.weights(keys)

    seq = Sequence(h.num_nodes, tuple(h.snapshots), tuple(h.additions),
                   tuple(h.deletions))
    run.store = timed_store(run, seq)
    log(f"history: scale {g['scale']} ({h.num_nodes} vertices, "
        f"{h.snapshots[0].shape[0]} edges in snapshot 0), {g['snapshots']} "
        f"snapshots, {g['changes_per_transition']} changes per transition, "
        f"generated in {time.perf_counter() - t0:.3f}s")


def check_answers(run: Run, answers) -> int:
    """Mismatched vertex values, summed over the sampled answers."""
    h = run.history
    ref = reference.Reference(h.snapshots, h.num_nodes, h.weights)
    return sum(reference.mismatched(values, ref.answer(a.window, a.alg,
                                                       a.source))
               for a, values in answers)


def sample_answers(run: Run) -> list:
    """A seeded sample of the answers, copied to the host."""
    import numpy as np
    k = min(run.config["check"]["answers"], len(run.answers))
    idx = sorted(run.rng(2).choice(len(run.answers), size=k, replace=False))
    return [(run.answers[i], np.asarray(run.answers[i].values)) for i in idx]


def metric_entries(bench: dict, cell: str, section: str) -> list:
    """The metrics of one section that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def tpu_devices(chips: int):
    """The TPU devices, or exit non-zero before any result is printed."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); the benchmark runs only on a chip")
    if len(devices) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX sees "
                 f"{len(devices)}")
    return devices


def configure_jax() -> None:
    """Persistent compile cache at a fixed path in the checkout, short programs too."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cell_files(bench: dict, cell_name: str) -> "tuple[dict, dict, dict]":
    """The cell's entry, its configuration and its traffic mix, by name."""
    cell = next(c for c in bench["workloads"] if c["name"] == cell_name)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return cell, config, traffic


def execute(bench: dict, cell_name: str, seed: int, seconds: float,
            trace: bool, devices, config=None, traffic=None) -> dict:
    """Set up, measure, check and reduce one run; returns the result object.

    ``config``/``traffic`` replace the cell's files (the CPU rehearsal
    passes tiny ones).
    """
    cell, cfg_file, traffic_file = cell_files(bench, cell_name)
    config = config or cfg_file
    traffic = traffic or traffic_file
    driver = load_file_module(HERE / "traffic" / f"{traffic['driver']}.py")
    run = Run(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace)
    run.compiles.install()
    sys.path.insert(0, str(ROOT / "src"))

    build_history(run)
    driver.setup(run)
    driver.window(run)
    log(f"window: {run.window_close_t - run.window_open_t:.3f}s, "
        f"compiles inside it: {run.window_compiles} "
        f"({run.compiles.total} in the run, {run.compiles.cache_hits} "
        f"persistent-cache hits)")
    if run.window_compiles:
        log("compiled inside the window: "
            + ", ".join(run.compiles.names[-run.window_compiles:]))

    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    sample = sample_answers(run)
    run.store = run.state = None
    run.answers = []
    gc.collect()

    t0 = time.perf_counter()
    mismatched = check_answers(run, sample)
    log(f"reference: {len(sample)} answers compared in "
        f"{time.perf_counter() - t0:.3f}s")
    limits = config["check"]["limits"]
    checks = {"mismatched_values": {"value": mismatched,
                                    "limit": limits["mismatched_values"]},
              "unanswered": {"value": run.unanswered,
                             "limit": limits["unanswered"]}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed}
    if trace:
        path = tracemod.find_xplane(TRACE_DIR)
        run.trace_data = tracemod.load(path) if path else None
        metrics = {}
        for m in metric_entries(bench, cell_name, "per_layer"):
            reader = load_file_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if run.trace_data is not None:
            device["busy_s"] = tracemod.busy_s(run.trace_data)
            device["window_s"] = tracemod.window_s(run.trace_data)
            result["breakdown"] = {
                "device_ops": tracemod.top_ops(run.trace_data),
                "idle_gaps": tracemod.idle_gaps(run.trace_data)}
    else:
        run.e2e["setup_s"] = run.setup_s
        metrics = {m["name"]: {"value": run.e2e[m["name"]], "unit": m["unit"]}
                   for m in metric_entries(bench, cell_name, "end_to_end")}
    result["metrics"] = metrics
    result["device"] = device
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"chipbench: no cell {args.workload!r} in BENCHMARK.json")
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("chipbench: the program (src/repro) is not in this checkout")
    devices = tpu_devices(cells[args.workload]["chips"])
    configure_jax()
    result = execute(bench, args.workload, args.seed, args.seconds,
                     bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
