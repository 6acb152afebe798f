"""What every cell shares: finding a cell's files by name, the chip
check, the compile cache, the compile counter, tracing and the result
line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
The configuration is ``bench/configs/<config>.json``; its ``driver`` key
names the module ``bench/<driver>.py`` that runs it, and its other keys
name the files beside it that hold its reference, its model or its
program. The mix is ``bench/traffic/<traffic>.json``, read by
``bench/traffic.py``. Each per-layer metric is read by
``bench/metrics/<metric>.py``, whose ``read(run)`` returns a number, or
``None`` where the run holds nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the checkout's own compile cache: a fixed path, since the directory
#: is part of every cache key
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / "bench-out"
#: the longest window a traced run measures: a serving window's trace
#: holds about 60,000 device operations a second, and reading 51 s of
#: them took a run past its time limit
TRACE_SECONDS = 20.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def metric_applies(metric: dict, cell: str, e2e_names) -> bool:
    """A metric with a ``workloads`` list belongs to those cells; one
    without belongs to every cell that reports the end-to-end metric it
    moves (or, for an end-to-end metric, to every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bm["configs"]}
    cfg_entry = cfgs[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    config.setdefault("config_dir", str((root / cfg_entry["file"]).parent))
    from bench import traffic as tf
    traffic = tf.load(w["traffic"], root / "bench" / "traffic")
    e2e = [m for m in bm["end_to_end"] if metric_applies(m, name, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if metric_applies(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def load_module(path: Path):
    """Import a file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    return load_module(path).read


def driver(config: dict):
    return importlib.import_module(f"bench.{config['driver']}")


def config_module(config: dict, key: str):
    """The module a configuration names under ``key`` (``reference``,
    ``model``, ``program``), a file beside the configuration's file."""
    where = Path(config.get("config_dir", BENCH / "configs"))
    return load_module(where / config[key])


def reference(config: dict):
    return config_module(config, "reference")


def prepare_process():
    """Environment the process needs before JAX loads: the TPU runtime
    logs inside the checkout, not under a fixed path of the host."""
    os.environ.setdefault("TPU_LOG_DIR", str(OUT_DIR / "tpu_logs"))
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def configure_client(cell: Cell):
    """Options the cell's mix gives the accelerator's runtime client
    (``client_options``), set before JAX makes the client."""
    opts = cell.traffic.get("client_options")
    if opts:
        import jax
        jax.config.update("jax_pjrt_client_create_options", dict(opts))


def require_chips(n: int):
    """The devices, or :class:`NoChip` when JAX runs on anything but an
    accelerator or sees fewer than ``n`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform not in ("tpu",):
        raise NoChip(f"no accelerator: JAX runs on "
                     f"{devices[0].platform!r}; there is no CPU path")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program however
    fast it compiled, so that only a checkout's first run compiles."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or str(CACHE_DIR)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs built while it is armed: XLA compiles, and loads
    from the persistent cache (both happen only when a shape or program
    is new to the process)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        self.seen: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1
            self.seen.append(event)

    @contextlib.contextmanager
    def armed_for(self):
        self.armed = True
        try:
            yield self
        finally:
            self.armed = False


def annotate(name: str):
    """A host span in the profiler's trace, named ``bench.<name>``."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def profile_options():
    """Device operations and the harness's annotations only: the Python
    tracer would record every call of the host loop and slow it several
    times, the runtime's own host events (level 2) run to millions in a
    serving window and take minutes to read, and the HLO protos are not
    read."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


class Traced:
    """A traced window's profile. ``trace`` is the reduced :class:`Trace`,
    read on first use after the window, with the program's ``repro.*``
    spans kept beside the harness's annotations; the raw trace is
    deleted once read. A serving loop closes its window and goes on
    serving the requests due in it: reading the profile at the close
    would hold them back."""

    def __init__(self, trace_dir: Optional[Path] = None):
        self.dir = trace_dir
        self._trace = None

    @property
    def trace(self):
        if self._trace is None and self.dir is not None:
            from bench import spans
            self._trace = spans.load(str(self.dir))
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
        return self._trace


@contextlib.contextmanager
def traced(enabled: bool, name: str):
    """Trace the body into ``bench-out/trace/<name>`` when ``enabled``;
    yields a :class:`Traced` (with no trace when not enabled)."""
    if not enabled:
        with annotate("window"):
            yield Traced()
        return
    import jax
    d = OUT_DIR / "trace" / name
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    jax.profiler.start_trace(str(d), profiler_options=profile_options())
    try:
        with annotate("window"):
            yield Traced(d)
    finally:
        jax.profiler.stop_trace()


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Outcome:
    """What a driver hands back from one run."""
    correct: bool
    attempted: int
    failed: int
    #: end-to-end metric name -> value, for the ``--trace 0`` line
    end_to_end: Dict[str, float]
    #: the numbers compared, each ``{"value": v, "limit": l}``
    checks: Dict[str, dict]
    memory_peak_bytes: Optional[int]
    #: what the per-layer readers read (``--trace 1``)
    run: Optional[object] = None
    #: host clock (``time.perf_counter``) when the window opened
    window_start: float = 0.0


def result_line(cell: Cell, out: Outcome, devices, setup_s: float,
                trace_on: bool) -> dict:
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    if trace_on:
        for m in cell.per_layer:
            v = metric_reader(m["name"])(out.run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
    else:
        vals = dict(out.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": float(vals[m["name"]]),
                                      "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    if trace_on and out.run is not None and out.run.trace is not None:
        from bench import trace as tr
        t = out.run.trace
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.window_s
        line["breakdown"] = tr.breakdown(t)
    line["checks"] = out.checks
    return line


def print_checks(checks: Dict[str, dict]):
    """The numbers compared, each beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
