"""One benchmark run of one cell: set-up, the measured window, the
check against the reference, and the result line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names,
its traffic mix in ``bench/traffic/<traffic>.json``, the driver that
mix names in ``bench/drivers/<driver>.py`` and each metric, end to end
or per layer, in ``bench/metrics/<name>.py``.  Adding a cell, a mix, a
driver or a metric adds files; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import graph500

TRAFFIC_DIR = "bench/traffic"
CACHE_DIR = "bench/.cache"
JAX_CACHE_DIR = "bench/.jax_cache"
TRACE_DIR = "bench/.traces"

#: JAX's own duration events for tracing, lowering, compiling and
#: reading the persistent compilation cache
COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
))


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips a cell needs."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------
# the cell, as data
# ---------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its
    configuration and traffic mix read from their own files."""
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    (entry,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / TRAFFIC_DIR / f"{w['traffic']}.json").read_text()
    )
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=_for_cell(spec["end_to_end"], workload),
        per_layer=_for_cell(spec["per_layer"], workload),
    )


def driver(cell: Cell):
    """The module ``bench.drivers.<driver>`` that the cell's mix names."""
    return importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")


def metric_reader(name: str) -> Callable:
    """``read`` of the metric module ``bench.metrics.<name>``."""
    return importlib.import_module(f"bench.metrics.{name}").read


# ---------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------


def find_chips(chips: int):
    """The first ``chips`` accelerators, their kind and published
    peaks; NoChip when JAX sees no TPU, too few, or an unknown kind."""
    import jax

    from bench.peaks import UnknownDevice, peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    try:
        peaks = peaks_for(kind)
    except UnknownDevice as e:
        raise NoChip(str(e)) from None
    return devices[:chips], kind, peaks


class CompileWatch:
    """Seconds JAX spends compiling (the union of its compile events)
    and how many such events came, from ``start()`` on."""

    def __init__(self):
        import jax

        self._spans: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            end = time.perf_counter()
            self._spans.append((end - duration, end))

    def start(self) -> None:
        self._spans = []

    @property
    def count(self) -> int:
        return len(self._spans)

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed directory inside
    the checkout, for every program however small."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str((Path(root) / JAX_CACHE_DIR).resolve()))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------


class Phases:
    """Host seconds of each step of set-up, printed as one line."""

    def __init__(self, t_start: float):
        self.t = t_start
        self.steps: list = []

    def done(self, name: str) -> float:
        now = time.perf_counter()
        self.steps.append((name, now - self.t))
        self.t = now
        return self.steps[-1][1]

    def line(self) -> str:
        parts = " ".join(f"{n}={s:.3f}" for n, s in self.steps)
        return f"set-up phases (s): {parts} total={sum(s for _, s in self.steps):.3f}"


@dataclasses.dataclass
class Setup:
    cell: Cell
    n: int
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    graph: object            # the program's Graph
    solver: object           # the program's Solver, or a stand-in
    partition_s: float
    device_bytes: int        # the engine program's bytes per chip
    compile_s: float = 0.0   # compile events in set-up, warm-up included


def program_bytes(solver, pg) -> int:
    """Device bytes per chip of the compiled engine program: arguments,
    outputs and temporaries, less what outputs alias of arguments."""
    from repro.api import get_processing
    from repro.core.engine import initial_state

    fn = solver.compiled(pg.n_parts, pg.n_local)
    state = initial_state(pg, get_processing("sssp"), [])
    ma = fn.lower(*pg.on_mesh(solver.mesh), *state).compile().memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def set_up(cell: Cell, root: Path, devices, watch: CompileWatch,
           phases: Phases) -> Setup:
    """Load the configuration's graph, partition and place it through
    the program's ``Solver`` and compile its engine; the compile events
    are counted from here on."""
    import jax

    from repro.api import Solver
    from repro.graph.formats import Graph
    from repro.launch.mesh import make_local_topology

    phases.done("import_program")
    cfg = cell.config
    n, src, dst, w, generated = graph500.cached_graph(
        cfg["generator"], cfg["scale"], Path(root) / CACHE_DIR
    )
    load_s = phases.done("generate" if generated else "load")
    say(f"graph {cfg['name']} n={n} m={len(src)} "
        f"{'generated' if generated else 'loaded'} in {load_s:.3f}s")
    graph = Graph(n, src, dst, w, name=cfg["name"])
    mesh = make_local_topology(cell.chips).mesh
    if set(mesh.devices.flat) != set(devices):
        raise NoChip(f"mesh {mesh.devices} is not the cell's devices")
    solver = Solver(cfg["spec"], mesh=mesh)

    pg = solver.partition(graph)
    jax.block_until_ready(pg.on_mesh(mesh))
    partition_s = phases.done("partition")
    say(f"partition+place {partition_s:.3f}s P={pg.n_parts} "
        f"R={pg.rows_per_rank} W={pg.width}")

    watch.start()
    device_bytes = program_bytes(solver, pg)
    phases.done("compile")
    return Setup(cell, n, src, dst, w, graph, solver, partition_s,
                 device_bytes)


# ---------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------


@dataclasses.dataclass
class Solve:
    key: int
    t0: float
    t1: float
    supersteps: int = 0
    converged: bool = False
    error: Optional[str] = None


@dataclasses.dataclass
class Window:
    solves: list
    t0: float
    t1: float
    compiles: int
    answers: list            # per solve, its answer (None if it failed)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def failed_solves(win: Window) -> int:
    """Solves that raised, or stopped before their fixpoint."""
    return sum(1 for r in win.solves if r.error or not r.converged)


# ---------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------


@dataclasses.dataclass
class RunRecord:
    """What a metric's ``read`` may look at."""
    cell: Cell
    setup: Setup
    setup_s: float
    window: Window
    reach: dict              # solve index -> (edges, vertices)
    trace: object            # xplane.TraceSummary, or None
    peaks: dict

    def reached(self) -> tuple[int, int]:
        """Edges and vertices reached, summed over the window's solves."""
        e = sum(self.reach[i][0] for i, r in enumerate(self.window.solves)
                if not r.error)
        v = sum(self.reach[i][1] for i, r in enumerate(self.window.solves)
                if not r.error)
        return e, v


def readings(run: RunRecord, metrics: list) -> dict:
    """Each metric's reading, by name; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python call tracing slows the host
    opts.host_tracer_level = 2
    return opts


def run(root: Path, workload: str, seed: int, seconds: float,
        trace: bool, t_start: float, look_for_chip: bool = True,
        peaks: Optional[dict] = None,
        wrap_solver: Optional[Callable] = None) -> dict:
    """One run of ``workload``; returns the result line as a dict.
    ``look_for_chip=False`` (tests only) accepts whatever devices JAX
    has, with the ``peaks`` given."""
    phases = Phases(t_start)
    import jax

    from bench.reference import Reference

    root = Path(root)
    cell = load_cell(root, workload)
    mix = driver(cell)
    phases.done("import")
    if look_for_chip:
        devices, kind, peaks = find_chips(cell.chips)
    else:
        devices = jax.devices()[:cell.chips]
        kind = devices[0].device_kind
    phases.done("init")
    say(f"jax {jax.__version__} {devices[0].platform} {kind} "
        f"x{len(devices)} workload={workload} seed={seed}")
    use_compile_cache(root)
    watch = CompileWatch()
    try:
        s = set_up(cell, root, devices, watch, phases)
        plan = mix.prepare(s, cell.traffic, seed)
        phases.done("warm_up")
        s.compile_s = watch.seconds
        say(f"compile+warm-up compile_s={s.compile_s:.3f} "
            f"events={watch.count} device_bytes={s.device_bytes}")
        if wrap_solver is not None:
            s.solver = wrap_solver(s.solver, s.n, s.src, s.dst, s.w)
        trace_dir = root / TRACE_DIR / workload
        annotate = None
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=trace_options())
            annotate = jax.profiler.TraceAnnotation
        phases.done("trace_start")
        setup_s = time.perf_counter() - t_start
        say(phases.line())
        win = mix.drive(s, plan, seconds, watch, annotate)
        if trace:
            jax.profiler.stop_trace()
    finally:
        watch.close()
    steps = [r.supersteps for r in win.solves]
    say(f"window {win.seconds:.3f}s solves={len(win.solves)} "
        f"compiles_in_window={win.compiles} supersteps "
        f"min={min(steps)} mean={sum(steps) / len(steps):.2f} "
        f"max={max(steps)}")
    for r in win.solves:
        if r.error:
            say(f"solve key={r.key} failed: {r.error}")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(st.get("peak_bytes_in_use", 0) for st in stats)
    say(f"device_bytes(memory_analysis)={s.device_bytes} "
        f"peak_bytes_in_use={peak}")
    s.solver = None  # frees the program's device graph before the check
    summary = None
    if trace:
        from bench import xplane

        pbs = sorted(trace_dir.glob("**/*.xplane.pb"))
        summary = xplane.summarize(xplane.load(pbs[-1])) if pbs else None
    t = time.perf_counter()
    checks, reach = mix.check(s, win, Reference(s.n, s.src, s.dst, s.w))
    say(f"reference check {time.perf_counter() - t:.3f}s")
    rec = RunRecord(cell, s, setup_s, win, reach, summary, peaks)
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": len(win.solves),
        "failed": failed_solves(win),
        "metrics": readings(rec, cell.per_layer if trace else cell.end_to_end),
        "device": {
            "platform": devices[0].platform, "kind": kind,
            "count": jax.device_count(), "memory_peak_bytes": peak,
        },
    }
    if trace and summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        top = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in top],
            "idle_gaps": [[n, t] for n, t in summary.gaps],
        }
    for c in checks:
        say(f"check {c.name}={c.value} limit={c.limit} "
            f"{'ok' if c.ok else 'FAILED'}")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result
