"""Tuned-config store: persist autopilot winners, auto-apply at startup.

Winners persist as ``TUNED.json`` keyed by ``(model-signature, backend,
mesh topology)`` — the same partitioning the XLA persistent cache uses, so
when the cache is placed from outside (``JAX_COMPILATION_CACHE_DIR``) the
file lives next to it and a warm boot picks up both the compiled
executables AND the knob settings that produced them.

Auto-apply contract (the startup half of the loop):

- ``fit`` / ``warmup`` / ``InferenceService.register`` / ``OnlineTrainer``
  call :func:`auto_apply` with their context; a matching entry's
  context-relevant call-knobs come back as arguments for the caller to use.
- **Explicit user settings always win**: a knob the caller received
  explicitly (constructor arg, or its env var set in the process
  environment) is passed in ``explicit`` and never overridden.
- Every application bumps ``dl4jtpu_tuned_config_applied_total`` (labelled
  by context) and rings a ``tuned_config_applied`` flight event; lookup or
  apply failures are swallowed — the autopilot must never break a training
  or serving startup.

Schema (``TUNED.json``)::

    {"version": 1,
     "configs": {
       "<sig12>/<backend>/<topology>": {
         "config": {"stage_window": 8, "telemetry_fetch_every": 20, ...},
         "objective": "fit", "metric": "train_samples_per_sec",
         "value": 6120.4, "trials": 9, "tuned_at": 1754300000.0}}}
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Dict, Optional, Sequence

from .knobs import get_knob, validate_config

__all__ = [
    "TUNED_FILENAME",
    "TUNED_PATH_ENV",
    "TunedStore",
    "auto_apply",
    "backend_name",
    "config_key",
    "model_signature",
    "topology_of",
    "tuned_path",
]

TUNED_FILENAME = "TUNED.json"
TUNED_PATH_ENV = "DL4JTPU_TUNED_PATH"  # explicit override, mostly for tests


def tuned_path() -> str:
    """Resolve the store location: explicit env override, else next to an
    externally placed XLA persistent cache, else the user cache dir."""
    explicit = os.environ.get(TUNED_PATH_ENV)
    if explicit:
        return explicit
    from ..runtime.compile_manager import CACHE_DIR_ENV

    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if cache_dir:
        return os.path.join(cache_dir, TUNED_FILENAME)
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "deeplearning4j_tpu", TUNED_FILENAME)


def model_signature(net_or_conf) -> str:
    """Stable 12-hex digest of the model architecture (conf JSON) — the
    same config always keys the same tuned entry, across processes."""
    conf = getattr(net_or_conf, "conf", net_or_conf)
    text = conf.to_json()
    return hashlib.sha256(text.encode("utf-8")).hexdigest()[:12]


def backend_name() -> str:
    try:
        import jax  # noqa: PLC0415

        return str(jax.default_backend())
    except Exception:  # jax not initializable: key degrades, never raises
        return "unknown"


def topology_of(net=None) -> str:
    """Mesh topology component of the key: the net's applied dp×fsdp×tp
    layout when one exists, else the flat local device count."""
    if net is not None:
        try:
            from ..parallel.layout import layout_of  # noqa: PLC0415

            layout = layout_of(net)
            if layout is not None:
                return (f"dp{int(layout.data)}.fsdp{int(layout.fsdp)}"
                        f".tp{int(layout.tp)}")
        except Exception:
            pass
    try:
        import jax  # noqa: PLC0415

        return f"d{int(jax.local_device_count())}"
    except Exception:
        return "d1"


def config_key(sig: str, backend: str, topology: str) -> str:
    return f"{sig}/{backend}/{topology}"


def key_for(net) -> str:
    return config_key(model_signature(net), backend_name(), topology_of(net))


class TunedStore:
    """One TUNED.json file: load tolerantly, write atomically, merge puts.

    ``put`` merges knob values into an existing entry's config (a fit-
    objective tune and a serve-objective tune of the same model coexist
    under one key); a malformed file on disk reads as empty rather than
    poisoning startup.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path if path is not None else tuned_path()
        self._lock = threading.Lock()

    # -------------------------------------------------------------- disk io
    def _load(self) -> dict:
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            return {"version": 1, "configs": {}}
        if not isinstance(data, dict) or not isinstance(
                data.get("configs"), dict):
            return {"version": 1, "configs": {}}
        return data

    def _save(self, data: dict) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)

    # ---------------------------------------------------------------- api
    def get(self, key: str) -> Optional[dict]:
        entry = self._load()["configs"].get(key)
        return entry if isinstance(entry, dict) else None

    def keys(self):
        return sorted(self._load()["configs"])

    def put(self, key: str, config: Dict[str, object], *,
            objective: str = "fit", metric: str = "",
            value: Optional[float] = None,
            trials: Optional[int] = None) -> dict:
        validate_config(config)
        with self._lock:
            data = self._load()
            entry = data["configs"].setdefault(key, {"config": {}})
            merged = dict(entry.get("config") or {})
            merged.update(config)
            entry["config"] = merged
            entry["objective"] = objective
            if metric:
                entry["metric"] = metric
            if value is not None:
                entry["value"] = float(value)
            if trials is not None:
                entry["trials"] = int(trials)
            entry["tuned_at"] = time.time()
            self._save(data)
            return entry

    def lookup(self, net) -> Optional[dict]:
        return self.get(key_for(net))


# ------------------------------------------------- warm-boot bundle slice
def tuned_slice(key: str, path: Optional[str] = None) -> Optional[dict]:
    """The raw TUNED.json entry for one config key — what a warm-boot
    bundle (fleet/artifacts.py) embeds so a fresh worker starts from the
    same tuned knobs as the process that built the bundle."""
    return TunedStore(path).get(key)


def install_slice(key: str, entry: dict,
                  path: Optional[str] = None) -> Optional[dict]:
    """Merge a bundle-carried TUNED.json slice into this process's store
    (validated, atomic, merge-on-put — same rules as the tuner's own
    writes). Returns the merged entry, or None when the slice is
    malformed/unknown-knobbed (a stale bundle must not poison startup)."""
    config = entry.get("config") if isinstance(entry, dict) else None
    if not isinstance(config, dict) or not config:
        return None
    try:
        return TunedStore(path).put(
            key, config,
            objective=str(entry.get("objective", "fit")),
            metric=str(entry.get("metric", "")),
            value=entry.get("value"),
            trials=entry.get("trials"))
    except Exception:  # noqa: BLE001 - tolerate foreign/stale slices
        return None


# ------------------------------------------------------------- auto-apply
def _applied_counter():
    from ..telemetry import get_registry  # noqa: PLC0415

    return get_registry().counter(
        "dl4jtpu_tuned_config_applied_total",
        "tuned-config knobs auto-applied at startup, by context",
        labelnames=("context",))


def auto_apply(net, context: str, explicit: Sequence[str] = (),
               path: Optional[str] = None) -> Dict[str, object]:
    """Return the tuned call-knob values for ``context``, minus any the
    caller marked explicit; apply in-place what can be applied here.

    Only *call*-kind knobs participate — env knobs are scoped to searches
    and must never be written process-globally at startup. An env knob's
    tuned value still reaches the caller when it doubles as a constructor
    argument (``serve_max_delay_ms``/``serve_max_batch`` in
    ``InferenceService.register``): such names may appear in the entry and
    are returned when ``context`` lists them and the process env does not
    already set the var (env set by the user = explicit).

    ``telemetry_fetch_every`` is applied directly here (the net's attached
    Telemetry session, unless the user constructed it with an explicit
    cadence). Everything else comes back as a dict for the caller to
    thread. Returns ``{}`` on any failure — startup never breaks.
    """
    try:
        store = TunedStore(path)
        entry = store.lookup(net)
        if not entry:
            return {}
        config = entry.get("config") or {}
        applied: Dict[str, object] = {}
        explicit = set(explicit)
        for name, value in config.items():
            try:
                knob = get_knob(name)
            except KeyError:
                continue  # entry written by a newer build; skip unknowns
            if context not in knob.contexts or name in explicit:
                continue
            if knob.kind == "env":
                if os.environ.get(knob.env) is not None:
                    continue  # user's env setting wins
                applied[name] = value
                continue
            if name == "telemetry_fetch_every":
                tel = getattr(net, "telemetry", None)
                if tel is None or getattr(tel, "fetch_every_explicit", True):
                    continue
                tel.fetch_every = max(1, int(value))
                applied[name] = value
                continue
            applied[name] = value
        if applied:
            try:
                _applied_counter().labels(context=context).inc(len(applied))
                from ..telemetry.flight_recorder import get_flight_recorder  # noqa: PLC0415

                get_flight_recorder().record(
                    "tuned_config_applied", context=context,
                    key=key_for(net), knobs=sorted(applied))
            except Exception:  # observability never breaks auto-apply
                pass
        return applied
    except Exception:
        return {}
