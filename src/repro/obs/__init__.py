"""repro.obs — two-timescale observability (DESIGN.md §13).

Three pieces, one run directory:

* :mod:`~repro.obs.trace` — a span tracer whose hierarchy mirrors the
  paper's timescales (``run > round > interval`` for training; ``run >
  step > {admission, prefill_chunk, decode_step}`` for serving),
  exported as zero-dep Chrome-trace JSON. Every span, from every sink,
  is also a ``repro.<name>`` annotation on the profiler's clock.
* :mod:`~repro.obs.telemetry` — jit-safe aux-metric probes (per-cluster
  consensus divergence, post-mixing residual, dispersion, grad norms)
  plus host-side ``core/theory.py`` bound gauges (``sigma_t``,
  Proposition 1, Lemma 1) so bound-vs-actual is one JSONL stream.
* :mod:`~repro.obs.manifest` — the run manifest (config hash, git SHA,
  mesh, backend) written next to every JSONL/trace.

``make_obs(trace_dir)`` builds the whole sink; ``NULL_OBS`` is the
disabled default every instrumented call site holds: it keeps nothing,
and its spans reach only a running profiler.
"""
from repro.obs.sink import NULL_OBS, Observability, ObsConfig, make_obs
from repro.obs.trace import Tracer, profiler_trace, validate_chrome_trace
from repro.obs.manifest import (
    config_hash, git_sha, mesh_info, write_manifest)
from repro.obs.telemetry import (
    TheoryGauges, default_constants, make_divergence_probe,
    make_scale_grad_probe, make_sim_grad_probe, sigma_t_general)

__all__ = [
    "NULL_OBS", "ObsConfig", "Observability", "TheoryGauges", "Tracer",
    "config_hash", "default_constants", "git_sha",
    "make_divergence_probe", "make_obs", "make_scale_grad_probe",
    "make_sim_grad_probe", "mesh_info", "profiler_trace",
    "sigma_t_general", "validate_chrome_trace", "write_manifest",
]
