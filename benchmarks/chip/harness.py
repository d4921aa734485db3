"""What every cell's run shares: finding the cell's files by name, the
device check, the compile cache, compile counting, the profiler window,
the metric readers and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its config
is ``configs/<config>.json``, whose ``family`` names the yardstick
``families/<family>.py`` and the costs ``costs/<family>.py``; its
traffic is ``traffic/<traffic>.json``, whose ``kind`` names the driver
``drivers/<kind>.py`` and whose ``mesh`` and ``fsdp`` say how the
cell's chips are laid out; each per-layer metric is read by
``metrics/<name>.py``.  Nothing here lists cells, configs, families,
mixes or metrics.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import model
from model import ConfigMismatch

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def limits(cell: str, smoke: bool = False) -> dict:
    """The limit of each number compared in ``cell``; with ``smoke``, at
    the program's smoke size (the CPU tests)."""
    return load_json(HERE / "checks" / f"{cell}.json")[
        "smoke" if smoke else "limits"]


def load_module(kind: str, name: str):
    """``drivers/<name>.py``, ``metrics/<name>.py``, ``costs/<name>.py``
    or ``families/<name>.py`` (names may hold dots, so they are loaded by
    path)."""
    path = HERE / kind / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[spec.name]
        raise
    return mod


def family(name: str):
    """The yardstick of a model family: ``families/<name>.py``."""
    return load_module("families", name)


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def prepare_env() -> None:
    """Before JAX is imported: the compile cache inside the checkout (the
    program honours ``JAX_COMPILATION_CACHE_DIR``), and no TPU log files
    outside it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def device_info(chips: int) -> dict:
    """The accelerator as JAX reports it; raises :class:`NoDevice` off a
    TPU or short of chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX reports platform "
                       f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"cell asks for {chips} chips, JAX reports "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache; a
    window reads ``n`` when it opens and when it closes."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.n = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, _secs, **_):
        if name == self.NAMES[0]:
            with self._lock:
                self.n += 1

    def _ev(self, name, **_):
        if name == self.NAMES[1]:
            with self._lock:
                self.n += 1


class Profile:
    """A traced stretch of the window: a host span marks its ends on the
    trace's own clock.  The trace is read once and deleted; an earlier
    run's is deleted before this one starts."""

    MARK = "bench_trace_window"

    def __init__(self, log_dir: Path):
        self.log_dir = log_dir
        self._ann = None
        self._t_mark = 0.0            # perf_counter as the mark opened

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.enable_hlo_proto = False
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(self.MARK)
        self._t_mark = time.perf_counter()
        self._ann.__enter__()

    def stop(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self, work=None):
        """The traced stretch reduced; ``work``: the ``(start, end)``
        stretches, in ``perf_counter`` seconds, in which the system had
        work (by default all of it)."""
        import trace
        events = trace.read(trace.find_trace(str(self.log_dir)))
        shutil.rmtree(self.log_dir, ignore_errors=True)
        marks = [e for e in events if e.name == self.MARK]
        if len(marks) != 1:
            raise RuntimeError(f"{len(marks)} window marks in the trace")
        m = marks[0]
        if work is not None:
            work = [(m.start_ns + (s - self._t_mark) * 1e9,
                     m.start_ns + (e - self._t_mark) * 1e9) for s, e in work]
        rest = [e for e in events if e.name != self.MARK]
        return trace.summarize(rest, m.start_ns, m.end_ns, work=work)


@dataclass
class Context:
    """What a driver gets: the cell, its config and traffic, and the
    run's arguments."""
    cell: str
    conf: dict                    # configs/<config>.json
    spec: object                  # the family's Spec of conf
    arch_cfg: object              # the program's ArchConfig, cut to conf
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float              # perf_counter at process start
    out_dir: Path
    limits: dict = field(default_factory=dict)   # checks/<cell>.json
    smoke: bool = False           # the program's smoke-size config (tests)
    device: dict = field(default_factory=dict)   # as JAX reports it
    compiles: CompileCounter | None = None
    chips: int = 1
    mesh: object = None           # the (data, model) mesh of the chips
    mesh_spec: object = None      # the MeshSpec the plan is derived for
    fsdp: bool = False            # the plan also shards weights over data

    @staticmethod
    def note(msg: str) -> None:
        """A line for standard error, ahead of the result."""
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Outcome:
    """What a driver returns.  ``e2e`` holds host-clock metrics; ``facts``
    is what the per-layer readers read; ``checks`` the numbers compared,
    each ``[name, value, limit]`` (value must not exceed limit)."""
    e2e: dict
    facts: dict
    attempted: int
    failed: int
    checks: list
    setup_s: float
    compiles_in_window: int

    @property
    def correct(self) -> bool:
        return self.failed == 0 and bool(self.checks) and all(
            v is not None and v <= lim for _, v, lim in self.checks)


def result_line(bench: dict, cell: str, out: Outcome, device: dict,
                trace: bool, trace_summary=None) -> dict:
    """The last line of standard output."""
    metrics = {}
    if not trace:
        for m in cell_metrics(bench, cell, "end_to_end"):
            v = out.setup_s if m["name"] == "setup_s" else out.e2e.get(
                m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        facts = dict(out.facts, trace=trace_summary, device=device)
        for m in cell_metrics(bench, cell, "per_layer"):
            v = load_module("metrics", m["name"]).read(facts)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if trace_summary is not None:
        line["device"] = dict(device, busy_s=trace_summary.busy_s,
                              window_s=trace_summary.window_s)
        line["breakdown"] = {"device_ops": trace_summary.top_ops,
                             "idle_gaps": trace_summary.idle_gaps}
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim in out.checks}
    return line


def mesh_shape(traffic: dict, chips: int) -> tuple[int, int]:
    """The mix's ``mesh`` (``{"data": d, "model": m}``, ``d * m`` the
    cell's chips), by default ``(data=chips, model=1)``."""
    shape = traffic.get("mesh", {"data": chips, "model": 1})
    if shape["data"] * shape["model"] != chips:
        raise ConfigMismatch(f"mesh {shape} is not the cell's {chips} chips")
    return shape["data"], shape["model"]


def cell_mesh(shape: tuple[int, int]):
    """The ``(data, model)`` mesh over the first ``data * model`` devices
    and the ``MeshSpec`` the plan is derived for."""
    from repro.core import MeshSpec
    from repro.launch.mesh import make_host_mesh
    d, m = shape
    return make_host_mesh((d, m)), MeshSpec((("data", d), ("model", m)))


def cli_context(cell_name: str, seed: int, seconds: float, trace: bool,
                t_process: float) -> Context:
    """Everything before the driver: the config, its cut and its check
    against the program's, and the mix's mesh (each raises
    :class:`ConfigMismatch` before any work), the device check (raises
    :class:`NoDevice`), the compile cache, the mesh, the traffic and the
    limits."""
    bench = benchmark()
    cell = workload(bench, cell_name)
    prepare_env()
    from repro.configs import get_config
    conf = model.load_config(cell["config"])
    spec, arch_cfg = model.cut(conf, family(conf["family"]),
                               get_config(conf["arch"]))
    mix = traffic(cell["traffic"])
    shape = mesh_shape(mix, cell["chips"])
    device = device_info(cell["chips"])
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    mesh, mesh_spec = cell_mesh(shape)
    out_dir = OUT_DIR / cell_name
    out_dir.mkdir(parents=True, exist_ok=True)
    return Context(cell=cell_name, conf=conf, spec=spec, arch_cfg=arch_cfg,
                   traffic=mix, seed=seed, seconds=seconds, trace=trace,
                   t_process=t_process, out_dir=out_dir,
                   limits=limits(cell_name), device=device,
                   compiles=CompileCounter(), chips=cell["chips"],
                   mesh=mesh, mesh_spec=mesh_spec,
                   fsdp=bool(mix.get("fsdp", False)))


def trace_span(traffic: dict, t_open: float, seconds: float
               ) -> tuple[float, float]:
    """The traced stretch of a window: the mix's ``trace_offset_s`` and
    ``trace_s``, kept inside the window."""
    offset = min(traffic.get("trace_offset_s", 0.0), seconds / 2)
    length = min(traffic.get("trace_s", seconds), seconds - offset)
    return t_open + offset, t_open + offset + length
