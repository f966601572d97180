"""What every cell shares: the chip check, the compile cache and clock,
the profiler session, loading a cell's files by name, and the result
line.

Nothing here knows a cell. A cell is the entry of ``BENCHMARK.json``
that names a configuration (``bench/configs/<config>.json``), a traffic
mix or training job (``bench/traffic/<traffic>.json``, whose ``runner``
names ``bench/runners/<runner>.py``) and the limits of its output check
(``bench/limits/<cell>.json``). A per-layer metric is the reader
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import gc
import glob
import importlib
import importlib.util
import json
import math
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# the persistent compile cache: a fixed path inside the checkout, so
# that the cell's second run finds every program the first compiled
CACHE_DIR = ROOT / ".jax_cache"
# profiler traces of ``--trace 1`` runs, deleted once read
TRACE_DIR = ROOT / ".bench_out" / "trace"
# the longest window a ``--trace 1`` run traces
TRACE_WINDOW_S = 8.0


class NoChip(SystemExit):
    """The run cannot measure: no TPU, or fewer chips than the cell asks."""


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise NoChip(2)


# ---------------------------------------------------------------------------
# loading a cell by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        fail(f"no {path.name} at {ROOT}")
    return load_json(path)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/limits/<cell>.json
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """Does ``cell`` report ``metric``? A metric with a ``workloads``
    list names its cells; one without is in every cell that reports the
    end-to-end metric it moves (every cell, for an end-to-end one)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def load_cell(spec: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r}; BENCHMARK.json has "
             f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if reports(m, name, names)]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=load_json(ROOT / conf["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def load_file_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """The plain reference named by a configuration file."""
    return importlib.import_module(f"bench.configs.{config['reference']}")


def flops_module(config: dict):
    return importlib.import_module(f"bench.flops.{config['flops']}")


def metric_reader(name: str) -> Callable:
    return load_file_module(BENCH_DIR / "metrics" / f"{name}.py",
                            f"bench_metric_{name.replace('.', '_')}").read


# ---------------------------------------------------------------------------
# the chip, the compile cache and the compile clock
# ---------------------------------------------------------------------------

def require_devices(chips: int):
    """The cell's devices; exits non-zero without a TPU or with fewer
    chips than the cell asks for. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def init_compile_cache() -> str:
    """Keep JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed ``<checkout>/.jax_cache``; cache every
    program, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds and count of XLA backend compiles (cache hits included:
    a hit still reports the event, with its load time)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.total = 0.0
        self.count = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self._event:
            self.total += secs
            self.count += 1


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devs)


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def free_device_memory() -> None:
    gc.collect()
    import jax
    jax.clear_caches()


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation; +inf
    entries (requests that never answered) count as the largest."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if v[hi] == math.inf:
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


# ---------------------------------------------------------------------------
# a run's context and result
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared with its limit: correct iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)     # nan fails


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    devices: list
    clock: CompileClock
    t_start: float                          # process start, perf_counter
    profiler: Optional["Profiler"] = None
    info: dict = field(default_factory=dict)  # earlier-line findings

    def note(self, **kw) -> None:
        """Record findings for the result's earlier line; they are
        echoed to standard error as they come, so a run that fails
        still shows how far it got."""
        self.info.update(kw)
        print(json.dumps({"note": kw}, default=_json_default),
              file=sys.stderr, flush=True)


@dataclass
class Outcome:
    """What a runner hands back: end-to-end values by name, the
    requests or steps attempted and failed, the output checks, and the
    readings the per-layer metrics take from the traced window."""
    metrics: dict
    attempted: int
    failed: int
    checks: list
    readings: dict = field(default_factory=dict)
    memory_peak_bytes: int = -1


class Profiler:
    """One profiler session around the traced part of the window."""

    def __init__(self, logdir: Path = TRACE_DIR):
        self.logdir = logdir
        self.active = False
        self.path: Optional[str] = None

    def start(self) -> None:
        import shutil
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        self.logdir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the benchmark's spans suffice
        jax.profiler.start_trace(str(self.logdir), profiler_options=opts)
        self.active = True

    def stop(self) -> None:
        import jax
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            found = glob.glob(str(self.logdir / "**" / "*.xplane.pb"),
                              recursive=True)
            self.path = found[0] if found else None

    def cleanup(self) -> None:
        import shutil
        shutil.rmtree(self.logdir, ignore_errors=True)


def annotate(name: str, **kw):
    """A host span in the profiler's trace (no cost worth counting when
    no trace is running)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


def emit_info(fields: dict) -> None:
    print(json.dumps({"info": fields}, default=_json_default), flush=True)


def _json_default(o):
    try:
        import numpy as np
        if isinstance(o, np.generic):
            return o.item()
        if isinstance(o, np.ndarray):
            return o.tolist()
    except ImportError:
        pass
    return str(o)


def finite(x: float) -> float:
    """JSON has no infinity: an unanswered tail prints as a huge number
    rather than as a string a JSON reader cannot take as a number."""
    return x if math.isfinite(x) else 1e300
