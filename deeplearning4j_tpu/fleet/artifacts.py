"""Warm-boot bundles: what a fresh fleet worker needs to skip the compile storm.

A bundle is one JSON sidecar living NEXT TO the checkpoints (via
``CheckpointStore.artifact_path``), keyed per (model-signature, backend,
topology) — the same key family as TUNED.json, because the compiled
program set is a function of exactly those three. It carries:

- the **XLA persistent-cache dir** the builder compiled into. A worker
  never takes its cache dir from a bundle — the compile manager's one
  resolver places it (``JAX_COMPILATION_CACHE_DIR``, else
  ``<repo>/.jax_cache``), the same place for every process of a host —
  the record only says whether the worker will find the builder's
  programs on disk (tiny CPU programs stay under jax's
  min-compile-time floor anyway, which is why the ready contract below
  does not depend on the disk cache);
- **kernel selections**: pinned site→variant overrides plus the
  KERNEL_CALIBRATION.json ratio snapshot, so the worker's auto scoring
  applies the same measured discounts;
- the **TUNED.json slice** for the model's config key (micro-batcher +
  admission knobs land through the normal ``auto_apply`` path);
- the **warmup spec**: pow2 row-bucket list, example trailing
  shape/dtype and the argmax flag — the worker compiles every bucket
  BEFORE reporting ready, so its first live request pays zero backend
  compiles (the jax.monitoring counter pins this, PR 3/7 proof style).

``build_bundle`` captures all of it from a live process (the trainer or
a CLI), ``save_bundle``/``load_bundle`` move it through the checkpoint
directory, ``install_bundle`` applies it inside a fresh worker before
first traffic.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

import numpy as np

__all__ = ["BUNDLE_VERSION", "build_bundle", "bundle_filename",
           "install_bundle", "load_bundle", "save_bundle"]

BUNDLE_VERSION = 1


def bundle_filename(signature: str, backend: str, topology: str) -> str:
    return f"warmboot-{signature}.{backend}.{topology}.json"


def _store_dir(store_or_dir) -> str:
    return getattr(store_or_dir, "directory", None) or str(store_or_dir)


def _example_spec(net, example) -> tuple:
    """(trailing shape, dtype name) of one request row. Derived from the
    net's declared input type when no example is given."""
    if example is not None:
        example = np.asarray(example)
        return tuple(int(d) for d in example.shape[1:]), str(example.dtype)
    it = getattr(net.conf, "input_type", None)
    if it is None or getattr(it, "kind", None) != "ff":
        raise ValueError(
            "build_bundle needs example= for non-feed-forward models "
            "(the warmup spec records one request's trailing shape)")
    return (int(it.size),), "float32"


def build_bundle(net, *, model: str = "default", example=None,
                 argmax: bool = True,
                 max_batch: Optional[int] = None) -> dict:
    """Capture a warm-boot bundle from THIS process for ``net``.

    ``max_batch`` bounds the warmup bucket list (default: the same
    env → TUNED.json → 64 resolution the micro-batcher will apply in
    the worker). ``argmax=True`` also warms the fused-argmax variants.
    """
    from ..ops import kernel_select as _ks  # noqa: PLC0415
    from ..runtime.compile_manager import (next_pow2,  # noqa: PLC0415
                                           persistent_cache_dir)
    from ..serving.batcher import MAX_BATCH_ENV  # noqa: PLC0415
    from ..tune import store as _tuned  # noqa: PLC0415

    sig = _tuned.model_signature(net)
    backend = _tuned.backend_name()
    topology = _tuned.topology_of(net)
    key = _tuned.config_key(sig, backend, topology)
    tuned_entry = _tuned.tuned_slice(key)

    if max_batch is None:
        raw = os.environ.get(MAX_BATCH_ENV)
        if raw is not None:
            max_batch = int(float(raw))
        elif tuned_entry and isinstance(tuned_entry.get("config"), dict):
            max_batch = tuned_entry["config"].get("serve_max_batch")
    if max_batch is None:
        max_batch = 64
    cap = next_pow2(int(max_batch))
    buckets, rows = [], 1
    while rows <= cap:
        buckets.append(rows)
        rows *= 2

    shape, dtype = _example_spec(net, example)
    cal_path, cal_data = _ks.calibration_snapshot()
    return {
        "bundle_version": BUNDLE_VERSION,
        "built_at": time.time(),
        "model": str(model),
        "signature": sig,
        "backend": backend,
        "topology": topology,
        "xla_cache_dir": persistent_cache_dir(),
        "kernel": {
            "calibration_path": cal_path,
            "calibration": cal_data,
            "site_overrides": _ks.site_overrides(),
        },
        "tuned": ({"key": key, "entry": tuned_entry}
                  if tuned_entry else None),
        "warmup": {
            "buckets": buckets,
            "max_batch": int(max_batch),
            "example_shape": list(shape),
            "example_dtype": dtype,
            "argmax": bool(argmax),
        },
    }


def save_bundle(store_or_dir, bundle: dict) -> str:
    """Atomically persist ``bundle`` next to the checkpoints; returns the
    path. One file per (signature, backend, topology) — a newer bundle
    for the same key replaces the old one."""
    directory = _store_dir(store_or_dir)
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, bundle_filename(
        bundle["signature"], bundle["backend"], bundle["topology"]))
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(bundle, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_bundle(store_or_dir, net=None, *,
                signature: Optional[str] = None,
                backend: Optional[str] = None,
                topology: Optional[str] = None) -> Optional[dict]:
    """Find the bundle matching ``net`` (or the explicit key parts) in a
    checkpoint directory. Key parts left unspecified match any single
    candidate — a worker that restored the net can match purely on the
    config signature even if the builder ran on another backend. Returns
    None when no bundle (or an ambiguous set) is found."""
    from ..tune import store as _tuned  # noqa: PLC0415

    directory = _store_dir(store_or_dir)
    if net is not None:
        signature = signature or _tuned.model_signature(net)
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return None
    hits = []
    for name in names:
        if not (name.startswith("warmboot-") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(directory, name)) as f:
                bundle = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(bundle, dict):
            continue
        if int(bundle.get("bundle_version", 0)) > BUNDLE_VERSION:
            continue  # newer schema than this code: skip, don't guess
        if signature and bundle.get("signature") != signature:
            continue
        if backend and bundle.get("backend") != backend:
            continue
        if topology and bundle.get("topology") != topology:
            continue
        hits.append(bundle)
    if len(hits) != 1:
        return None
    return hits[0]


def install_bundle(bundle: dict) -> dict:
    """Apply a bundle inside a FRESH worker, before first traffic.

    Order matters: the XLA cache must be placed before the first jax
    compile, the calibration/tuned state before ``register()`` runs
    ``auto_apply``. Returns a report of what was installed plus the
    bundle's warmup spec (the worker drives ``InferenceService.warmup``
    from it, then arms the compile counter and reports ready).
    """
    from ..ops import kernel_select as _ks  # noqa: PLC0415
    from ..runtime.compile_manager import (  # noqa: PLC0415
        resolve_persistent_cache)
    from ..tune import store as _tuned  # noqa: PLC0415

    report = {"xla_cache": False, "calibration": False,
              "site_overrides": 0, "tuned": False}

    # True when this worker's cache is the directory the builder compiled
    # into, i.e. its programs will be found on disk
    report["xla_cache"] = (
        resolve_persistent_cache() == bundle.get("xla_cache_dir"))

    kernel = bundle.get("kernel") or {}
    cal = kernel.get("calibration") or {}
    if cal:
        path = _ks._calibration_path()  # noqa: SLF001 - same package family
        if not os.path.exists(path):
            try:
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(cal, f, indent=1, sort_keys=True)
                os.replace(tmp, path)
                report["calibration"] = True
            except OSError:
                pass
    for site, variant in (kernel.get("site_overrides") or {}).items():
        _ks.set_site_override(str(site), str(variant))
        report["site_overrides"] += 1

    tuned = bundle.get("tuned") or None
    if tuned and tuned.get("key") and tuned.get("entry"):
        report["tuned"] = _tuned.install_slice(
            str(tuned["key"]), tuned["entry"]) is not None

    report["warmup"] = dict(bundle.get("warmup") or {})
    return report
