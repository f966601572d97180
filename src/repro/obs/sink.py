"""The observability sink: tracer + one JSONL metrics stream + manifest.

One :class:`Observability` object per run directory. It owns

* a :class:`~repro.obs.trace.Tracer` exported to ``trace.json``
  (Chrome trace / Perfetto),
* ONE ``metrics.jsonl`` stream (a :class:`~repro.train.metrics.
  MetricLogger`) that every record kind shares — train rows, theory
  gauges, comms attribution, serving latency — so bound-vs-actual for
  a round is a single grep,
* a ``manifest.json`` (config hash, git SHA, mesh, backend) written at
  construction,
* with ``profile``, a ``jax.profiler`` session writing ``jax_profile/``.

Spans, instants and counters enter the profiler's clock as
``repro.<name>`` annotations from every sink, ``NULL_OBS`` included
(``repro.obs.trace``): under any profiler session — this sink's, or one
an outside harness started — the program's host spans lie beside the
device ops. Instrumented call sites hold ``NULL_OBS`` by default — it
keeps nothing, writes no file, runs no probe and adds no device sync;
a span costs about a microsecond when no profiler session runs — and
are handed a recording sink via ``make_obs(trace_dir, ...)``.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import Any, Optional

from repro.obs import trace
from repro.obs.manifest import write_manifest

# NOTE: repro.train.metrics is imported lazily inside
# Observability.__init__ — a top-level import would cycle
# (train/__init__ -> trainer -> obs.sink -> train.metrics ->
# train/__init__) whenever the import starts from repro.train.


@dataclass
class ObsConfig:
    trace_dir: Optional[str] = None     # None = observability off
    profile: bool = False               # start a jax.profiler session
    window: int = 100                   # MetricLogger smoothing window
    console_every: int = 0              # 0 = JSONL only, no console


class _NullObs:
    """The disabled sink — safe to call everywhere, keeps nothing; its
    spans, instants and counters reach only a running profiler."""
    enabled = False
    tracer = None
    metrics = None

    def span(self, name: str, counters=None, **args: Any):
        return trace.span(name, self, counters, **args)

    def instant(self, name: str, **args: Any) -> None:
        trace.mark(name, **args)

    def counter(self, name: str, **values: Any) -> None:
        trace.mark("counter." + name, **values)

    def emit(self, kind: str, step: int, **fields: Any) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL_OBS = _NullObs()


def _jsonable(v: Any) -> Any:
    if hasattr(v, "tolist"):
        return v.tolist()
    if hasattr(v, "__float__") and not isinstance(v, (int, bool, float)):
        return float(v)
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return v


class Observability:
    enabled = True

    def __init__(self, cfg: ObsConfig, run_name: str = "run",
                 config: Any = None, extra: Optional[dict] = None):
        assert cfg.trace_dir, "Observability needs a trace_dir; " \
            "use NULL_OBS / make_obs(None) for the disabled sink"
        self.cfg = cfg
        self.dir = Path(cfg.trace_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.tracer = trace.Tracer()
        from repro.train.metrics import MetricLogger
        self.metrics = MetricLogger(str(self.dir / "metrics.jsonl"),
                                    console_every=cfg.console_every,
                                    window=cfg.window)
        self.manifest_path = write_manifest(
            str(self.dir), config=config,
            extra={"run": run_name, **(extra or {})})
        self._profiling = False
        if cfg.profile:
            # a --profile run without its device trace is a failed run:
            # let start_trace raise instead of running on untraced
            import jax
            jax.profiler.start_trace(str(self.dir / "jax_profile"))
            self._profiling = True
        self._closed = False

    # -- tracer passthrough -------------------------------------------------
    def span(self, name: str, counters=None, **args: Any):
        return self.tracer.open(name, args, counters, self)

    def instant(self, name: str, **args: Any) -> None:
        self.tracer.instant(name, **args)

    def counter(self, name: str, **values: Any) -> None:
        self.tracer.counter(name, **values)

    # -- telemetry ----------------------------------------------------------
    def emit(self, kind: str, step: int, **fields: Any) -> None:
        """One JSONL record tagged ``kind`` into the shared stream."""
        self.metrics.log(step, kind=kind,
                         **{k: _jsonable(v) for k, v in fields.items()})

    # -- lifecycle ----------------------------------------------------------
    def flush(self) -> None:
        """Export the Chrome trace collected so far (full rewrite)."""
        self.tracer.export(str(self.dir / "trace.json"))

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.flush()
        if self._profiling:
            import jax
            self._profiling = False
            jax.profiler.stop_trace()
        self.metrics.close()

    def __enter__(self) -> "Observability":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def make_obs(trace_dir: Optional[str], profile: bool = False,
             run_name: str = "run", config: Any = None,
             extra: Optional[dict] = None, window: int = 100,
             console_every: int = 0):
    """The one constructor call sites use: ``None`` → ``NULL_OBS``."""
    if not trace_dir:
        return NULL_OBS
    return Observability(
        ObsConfig(trace_dir=trace_dir, profile=profile, window=window,
                  console_every=console_every),
        run_name=run_name, config=config, extra=extra)


__all__ = ["NULL_OBS", "ObsConfig", "Observability", "make_obs"]
