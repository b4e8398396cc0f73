"""Performance autopilot: knob registry, search engine, tuned-config store.

PRs 5-9 built every ingredient of a tuning loop — the static roofline cost
model (``analysis/cost_model.py``), the kernel-variant registry with
per-site overrides (``ops/kernel_select.py``), the bench regression gate
(``scripts/bench_gate.py``), and the measured collective census. A human
still had to pick bucket granularity, staging windows, batcher delays and
kernel overrides by hand. This package closes the loop:

- :mod:`~deeplearning4j_tpu.tune.knobs` — every tunable surface registers a
  typed knob (domain, default, cost-model hint, apply semantics); env-var
  knobs only ever apply through scoped setters that restore on exit.
- :mod:`~deeplearning4j_tpu.tune.search` — successive halving over candidate
  configs, seeded and pruned by the roofline prior
  (``predicted_step_seconds``), with short measured trials whose warm-compile
  count is asserted zero so the search measures steady state.
- :mod:`~deeplearning4j_tpu.tune.store` — winners persist as ``TUNED.json``
  keyed by (model-signature, backend, mesh topology) next to
  ``JAX_COMPILATION_CACHE_DIR``; ``fit``/``warmup``/``InferenceService.register``/
  ``OnlineTrainer`` auto-apply a matching entry at startup (explicit user
  settings always win).

CLI: ``python -m deeplearning4j_tpu.tune --model mlp --budget 60s``.
See docs/performance.md ("Performance autopilot").
"""

from .knobs import EnvScope, Knob, all_knobs, get_knob, scoped_env
from .search import SearchResult, Trial, run_autotune, successive_halving
from .store import TunedStore, auto_apply, config_key, model_signature, tuned_path

__all__ = [
    "EnvScope",
    "Knob",
    "SearchResult",
    "Trial",
    "TunedStore",
    "all_knobs",
    "auto_apply",
    "config_key",
    "get_knob",
    "model_signature",
    "run_autotune",
    "scoped_env",
    "successive_halving",
    "tuned_path",
]
