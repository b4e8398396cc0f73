"""Versioned checkpoint store: durable model versions for the live loop.

The reference stack checkpoints through ``ModelSerializer`` to one path —
fine for batch jobs, useless for a continuously-training model that must
survive a NaN storm and hand fresh versions to serving without a restart.
This store adds the production contract on top of
``utils/serialization.write_model``'s container:

- **Atomic versions.** Every save writes to a temp file in the store
  directory and ``os.replace``s it into ``model-v<NNNNNNNN>.zip`` — a
  reader (or a crash mid-write) can never observe a torn checkpoint.
  Version ids are monotonic across process restarts (the scan resumes
  after the largest id on disk).
- **Exact resume.** The container carries params, optimizer moments,
  layer state and the iteration counter; the store appends the training
  RNG key as ``rng.npz``, so :meth:`load_into`/:meth:`restore` resume
  bit-identically — dropout draws included.
- **Retention.** ``retain`` bounds the directory: pruning happens after
  every successful save, oldest versions first, never the newest.
- **Non-blocking saves.** :meth:`save_async` captures a consistent
  snapshot on the caller's thread (device-side copies — one async copy
  dispatch, no host sync, and safe against donation recycling the live
  buffers) and serializes it on a background writer thread; the training
  loop never waits on the filesystem.
- **In-place rollback.** :meth:`load_into` loads a version's leaves back
  into a LIVE net without re-initializing it — the compile-manager token
  (and with it every cached executable) survives, so a rollback costs
  zero recompiles. A net living on a :class:`~..parallel.MeshLayout` gets
  its leaves re-placed on the layout's shardings.
- **Integrity + quarantine.** Every version carries a sha256-per-entry
  ``manifest.json`` written atomically with the zip. Restore paths
  (:meth:`restore`/:meth:`load_into`/worker boot) verify the manifest
  before deserializing; a corrupt or torn version is **quarantined**
  (renamed ``*.quarantine``, counted in
  ``dl4jtpu_checkpoint_corrupt_total``, never re-scanned as a version
  but still counted by the id scan so version numbers stay monotonic)
  and the restore falls back to the newest good version. Stale
  ``.tmp-v*`` files left by a killed writer are swept to quarantine at
  store construction.

See docs/streaming.md for the on-disk layout and the OnlineTrainer's
checkpoint/rollback semantics, docs/robustness.md for the integrity and
quarantine contract.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import re
import threading
import zipfile
from typing import Any, List, Optional

import numpy as np

from .resilience import Deadline, RetryPolicy

__all__ = ["CheckpointCorruptError", "CheckpointStore", "CheckpointInfo"]

_VERSION_RE = re.compile(r"^model-v(\d{8})\.zip$")
_QUARANTINE_RE = re.compile(r"^model-v(\d{8})\.zip\.quarantine$")
_TMP_RE = re.compile(r"^\.tmp-v(\d{8})-(\d+)$")

_MANIFEST_NAME = "manifest.json"


class CheckpointCorruptError(RuntimeError):
    """A stored version failed integrity verification."""


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        pass  # e.g. EPERM: someone else's live process
    return True


def _version_filename(version: int) -> str:
    return f"model-v{int(version):08d}.zip"


class CheckpointInfo:
    """One stored version: id, path, and the container's meta."""

    __slots__ = ("version", "path", "iteration", "epoch", "model_class",
                 "bytes")

    def __init__(self, version: int, path: str, meta: dict, size: int):
        self.version = int(version)
        self.path = path
        self.iteration = int(meta.get("iteration", 0))
        self.epoch = int(meta.get("epoch", 0))
        self.model_class = meta.get("model_class")
        self.bytes = int(size)

    def to_dict(self) -> dict:
        return {"version": self.version, "path": self.path,
                "iteration": self.iteration, "epoch": self.epoch,
                "model_class": self.model_class, "bytes": self.bytes}


class _Snapshot:
    """Leaf-reference snapshot a background writer can serialize.

    Device leaves are copied ON DEVICE at capture time (an async dispatch —
    the caller does not sync): the live net's buffers may be donated into
    the very next staged dispatch, and a donated buffer fetched later reads
    as deleted. The host fetch happens on the writer thread, inside
    ``np.savez``.
    """

    def __init__(self, model):
        import jax
        import jax.numpy as jnp

        def copy_leaf(a):
            if isinstance(a, jax.Array):
                return jnp.copy(a)
            if isinstance(a, np.ndarray):
                return np.array(a)
            return a

        snap = jax.tree_util.tree_map(copy_leaf,
                                      (model.params, model.opt_state,
                                       model.state, model._rng))
        self.params, self.opt_state, self.state, self.rng = snap
        self.conf = model.conf
        self.iteration = int(model.iteration)
        self.epoch = int(getattr(model, "epoch", 0))
        self.model_class = type(model).__name__

    def init(self) -> "_Snapshot":  # write_model contract
        return self


class CheckpointStore:
    """Directory of monotonic, atomically-written model versions."""

    def __init__(self, directory: str, *, retain: int = 5, registry=None,
                 chaos=None):
        if int(retain) < 1:
            raise ValueError(f"retain must be >= 1, got {retain}")
        self.directory = str(directory)
        self.retain = int(retain)
        self.chaos = chaos  # optional testing.chaos.FaultPlan hook
        os.makedirs(self.directory, exist_ok=True)
        self._lock = threading.Lock()
        self._next_version = self._scan_max() + 1
        self._writer: Optional[threading.Thread] = None
        self._write_error: Optional[BaseException] = None
        if registry is None:
            from ..telemetry import get_registry  # noqa: PLC0415

            registry = get_registry()
        self._m_saves = registry.counter(
            "dl4jtpu_online_checkpoints_total",
            "checkpoint versions written by the store")
        self._m_restores = registry.counter(
            "dl4jtpu_online_checkpoint_restores_total",
            "checkpoint restore/load_into operations")
        self._m_pruned = registry.counter(
            "dl4jtpu_online_checkpoints_pruned_total",
            "checkpoint versions removed by retention pruning")
        self._m_corrupt = registry.counter(
            "dl4jtpu_checkpoint_corrupt_total",
            "checkpoint versions quarantined after failing verification")
        self._io = RetryPolicy("checkpoint.io", max_attempts=3, base_s=0.05,
                               cap_s=1.0, retry_on=(OSError,),
                               registry=registry)
        self._sweep_stale_tmp()

    # ----------------------------------------------------------- directory
    def _scan_max(self) -> int:
        """Largest version id on disk — INCLUDING quarantined versions, so
        a quarantined id is never reissued to a new (different) save."""
        vmax = 0
        for name in os.listdir(self.directory):
            m = _VERSION_RE.match(name) or _QUARANTINE_RE.match(name)
            if m:
                vmax = max(vmax, int(m.group(1)))
        return vmax

    def _sweep_stale_tmp(self) -> int:
        """Quarantine ``.tmp-v*`` files whose writer pid is gone (a killed
        writer mid-``_write``). A live pid — including our own, which may
        carry an in-flight async writer from another store over this
        directory — is left alone. Returns the count swept."""
        swept = 0
        for name in sorted(os.listdir(self.directory)):
            m = _TMP_RE.match(name)
            if not m:
                continue
            if _pid_alive(int(m.group(2))):
                continue
            path = os.path.join(self.directory, name)
            try:
                os.replace(path, path + ".quarantine")
            except OSError:
                continue
            swept += 1
            self._m_corrupt.inc()
            self._flight("checkpoint_quarantined", file=name,
                         reason="stale temp file from dead writer")
        return swept

    def path(self, version: int) -> str:
        return os.path.join(self.directory, _version_filename(version))

    def _claim_version(self) -> int:
        """Next monotonic id: past both this store's counter AND whatever
        any other writer already put on disk (the rescan keeps concurrent
        stores over one directory from replacing each other's versions)."""
        with self._lock:
            version = max(self._next_version, self._scan_max() + 1)
            self._next_version = version + 1
            return version

    def versions(self) -> List[CheckpointInfo]:
        """All stored versions, oldest first (torn/foreign files ignored)."""
        out: List[CheckpointInfo] = []
        for name in sorted(os.listdir(self.directory)):
            m = _VERSION_RE.match(name)
            if not m:
                continue
            path = os.path.join(self.directory, name)
            try:
                with zipfile.ZipFile(path, "r") as zf:
                    meta = json.loads(zf.read("meta.json"))
                out.append(CheckpointInfo(int(m.group(1)), path, meta,
                                          os.path.getsize(path)))
            except Exception:  # noqa: BLE001 - a bad file is not a version
                continue
        return out

    def latest(self) -> Optional[CheckpointInfo]:
        vs = self.versions()
        return vs[-1] if vs else None

    def latest_version(self) -> int:
        """Newest version number, 0 when the store is empty — the poll
        primitive of the fleet's version-propagation bus (workers and the
        router compare it against what they serve)."""
        info = self.latest()
        return 0 if info is None else int(info.version)

    def artifact_path(self, filename: str) -> str:
        """Path for a sidecar artifact living NEXT TO the checkpoints
        (warm-boot bundles, notes). Sidecars never match _VERSION_RE, so
        version scans, retention pruning and restores ignore them."""
        if _VERSION_RE.match(filename):
            raise ValueError(
                f"{filename!r} would shadow a checkpoint version")
        return os.path.join(self.directory, filename)

    def wait_for_version(self, min_version: int, *,
                         timeout_s: float = 30.0,
                         poll_s: float = 0.25) -> Optional[CheckpointInfo]:
        """Block until the store publishes ``version >= min_version`` (the
        subscriber half of the checkpoint bus). Returns its info, or None
        on timeout. Polling, not inotify: the store is also written from
        other processes/filesystems where watches don't travel."""
        deadline = Deadline(timeout_s)
        while True:
            info = self.latest()
            if info is not None and info.version >= min_version:
                return info
            if not deadline.pace(poll_s):
                return None

    def stats(self) -> dict:
        """JSON-ready store view (the /api/online checkpoint listing)."""
        vs = self.versions()
        return {
            "directory": self.directory,
            "retain": self.retain,
            "versions": [v.to_dict() for v in vs],
            "latest_version": vs[-1].version if vs else None,
            "total_bytes": sum(v.bytes for v in vs),
        }

    # ---------------------------------------------------------------- save
    def _write(self, snapshot: _Snapshot, version: int) -> str:
        from ..utils.serialization import write_model  # noqa: PLC0415

        final = self.path(version)
        tmp = os.path.join(self.directory,
                           f".tmp-v{version:08d}-{os.getpid()}")

        def write_once():
            write_model(snapshot, tmp)
            with zipfile.ZipFile(tmp, "a", zipfile.ZIP_DEFLATED) as zf:
                # the rng key rides as an extra container entry so resume
                # replays the exact dropout chain
                buf = io.BytesIO()
                np.savez(buf, rng=np.asarray(snapshot.rng))
                zf.writestr("rng.npz", buf.getvalue())
                # sha256-per-entry manifest, inside the same atomic zip:
                # either the whole verified container lands or nothing does
                entries = {name: hashlib.sha256(zf.read(name)).hexdigest()
                           for name in zf.namelist()}
                zf.writestr(_MANIFEST_NAME, json.dumps(
                    {"algo": "sha256", "entries": entries}, sort_keys=True))
            os.replace(tmp, final)  # atomic: readers never see a torn file

        try:
            self._io.run(write_once)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
        if self.chaos is not None:
            self.chaos.fire("checkpoint.write", path=final,
                            directory=self.directory, version=version)
        self._m_saves.inc()
        self._flight("online_checkpoint", version=version,
                     iteration=snapshot.iteration, path=final)
        self.prune()
        return final

    @staticmethod
    def snapshot(model) -> _Snapshot:
        """Capture a consistent leaf snapshot of ``model`` NOW (device-side
        copies, no host sync). Hand it to :meth:`save`/:meth:`save_async` —
        and, in the live loop, the SAME snapshot to
        ``InferenceService.hot_swap``, so the version on disk and the
        version serving are bit-identical."""
        return _Snapshot(model)

    def save(self, model) -> CheckpointInfo:
        """Write one version synchronously; returns its info. ``model`` may
        be a live net or a :meth:`snapshot`."""
        snapshot = model if isinstance(model, _Snapshot) else _Snapshot(model)
        version = self._claim_version()
        path = self._write(snapshot, version)
        return CheckpointInfo(version, path,
                              {"iteration": snapshot.iteration,
                               "epoch": snapshot.epoch,
                               "model_class": snapshot.model_class},
                              os.path.getsize(path))

    def save_async(self, model) -> int:
        """Snapshot now (device-side copies, no host sync), serialize on a
        background thread; returns the version id that WILL exist once the
        writer lands. One writer at a time: a still-running previous write
        is joined first (saves are ordered, never interleaved). ``model``
        may be a live net or a :meth:`snapshot`."""
        self.join()
        snapshot = model if isinstance(model, _Snapshot) else _Snapshot(model)
        version = self._claim_version()

        def work():
            try:
                self._write(snapshot, version)
            except BaseException as e:  # surfaced on the next join()
                self._write_error = e

        self._writer = threading.Thread(
            target=work, daemon=True, name=f"dl4jtpu-ckpt-v{version}")
        self._writer.start()
        return version

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for an in-flight async save; re-raises its error, if any."""
        w = self._writer
        if w is not None:
            w.join(timeout=timeout)
            self._writer = None
        if self._write_error is not None:
            err, self._write_error = self._write_error, None
            raise err

    def prune(self) -> int:
        """Drop oldest versions beyond ``retain``; returns the count."""
        vs = self.versions()
        extra = vs[:-self.retain] if len(vs) > self.retain else []
        removed = 0
        for info in extra:
            try:
                os.remove(info.path)
                removed += 1
            except OSError:
                continue
        if removed:
            self._m_pruned.inc(removed)
        return removed

    # ----------------------------------------------------------- integrity
    def verify(self, version: int) -> str:
        """Check a stored version against its sha256 manifest.

        Returns ``"ok"`` (manifest verified) or ``"legacy"`` (pre-manifest
        container — accepted, nothing to check against). Raises
        :class:`CheckpointCorruptError` on a torn zip, a digest mismatch,
        or a manifest that disagrees with the zip's entry list.
        """
        path = self.path(int(version))
        try:
            with zipfile.ZipFile(path, "r") as zf:
                names = set(zf.namelist())
                if _MANIFEST_NAME not in names:
                    # testzip() names the first entry that fails its CRC or
                    # header check, it does not raise; and a directory torn
                    # short can list whole entries and still lack the model
                    bad = zf.testzip()
                    if bad is not None or "meta.json" not in names:
                        raise CheckpointCorruptError(
                            f"v{version}: no manifest and not a whole "
                            f"legacy container (bad entry {bad!r}, "
                            f"entries {sorted(names)})")
                    return "legacy"
                manifest = json.loads(zf.read(_MANIFEST_NAME))
                entries = dict(manifest.get("entries") or {})
                extra = names - set(entries) - {_MANIFEST_NAME}
                missing = set(entries) - names
                if extra or missing:
                    raise CheckpointCorruptError(
                        f"v{version}: manifest/zip mismatch "
                        f"(extra={sorted(extra)}, missing={sorted(missing)})")
                for name, digest in entries.items():
                    got = hashlib.sha256(zf.read(name)).hexdigest()
                    if got != digest:
                        raise CheckpointCorruptError(
                            f"v{version}: sha256 mismatch in {name!r}")
        except CheckpointCorruptError:
            raise
        except Exception as e:  # BadZipFile, truncated read, bad json...
            raise CheckpointCorruptError(f"v{version}: unreadable ({e!r})") from e
        return "ok"

    def quarantine(self, version: int, reason: str = "") -> str:
        """Rename a version out of the scan set (``*.quarantine``); it is
        never served again but its id stays claimed (see `_scan_max`)."""
        path = self.path(int(version))
        target = path + ".quarantine"
        try:
            os.replace(path, target)
        except FileNotFoundError:
            # Lost a cross-process race: another store over the same
            # directory (a sibling fleet worker) quarantined it first.
            return target
        self._m_corrupt.inc()
        self._flight("checkpoint_quarantined", version=int(version),
                     reason=reason or "verification failed")
        return target

    def _disk_versions(self) -> List[int]:
        """Raw version ids on disk, ascending — unlike :meth:`versions`
        this does NOT silently skip unreadable files, so a fully garbled
        newest version is still seen (and can be quarantined)."""
        out = []
        for name in os.listdir(self.directory):
            m = _VERSION_RE.match(name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def _open_verified(self, version: Optional[int], *,
                       fallback: bool) -> tuple:
        """Resolve (version, path), verifying integrity first. A corrupt
        version is quarantined; with ``fallback`` the walk continues to
        the next-newest good version, without it the corruption raises."""
        if version is not None:
            path = self.path(int(version))
            if not os.path.exists(path):
                raise FileNotFoundError(
                    f"checkpoint version {version} not in {self.directory!r} "
                    f"(have {self._disk_versions()})")
            try:
                self.verify(int(version))
                return int(version), path
            except CheckpointCorruptError as e:
                self.quarantine(int(version), reason=str(e))
                if not fallback:
                    raise
        for v in reversed(self._disk_versions()):
            try:
                self.verify(v)
                return v, self.path(v)
            except CheckpointCorruptError as e:
                self.quarantine(v, reason=str(e))
        raise FileNotFoundError(
            f"checkpoint store {self.directory!r} holds no intact versions")

    # ------------------------------------------------------------- restore
    def restore(self, version: Optional[int] = None, *,
                fallback: Optional[bool] = None):
        """Rebuild a FRESH model from a stored version (default: latest) —
        ``utils.serialization.restore_model`` plus the stored rng key.
        Verifies integrity first; a corrupt version is quarantined and,
        when no explicit version was pinned (or ``fallback=True``), the
        newest remaining good version is restored instead."""
        return self.restore_with_info(version, fallback=fallback)[0]

    def restore_with_info(self, version: Optional[int] = None, *,
                          fallback: Optional[bool] = None):
        """:meth:`restore`, returning ``(model, CheckpointInfo)`` — the
        fleet worker boot path, which must know WHICH version survived
        verification to advertise it on the bus."""
        from ..utils.serialization import restore_model  # noqa: PLC0415

        if fallback is None:
            fallback = version is None
        version, path = self._open_verified(version, fallback=fallback)
        model = restore_model(path)
        self._load_rng(model, path)
        self._m_restores.inc()
        with zipfile.ZipFile(path, "r") as zf:
            meta = json.loads(zf.read("meta.json"))
        return model, CheckpointInfo(version, path, meta,
                                     os.path.getsize(path))

    def load_into(self, model, version: Optional[int] = None, *,
                  fallback: Optional[bool] = None) -> int:
        """Roll a LIVE model back to a stored version in place.

        Loads params/opt-state/state/iteration/rng without ``init(force)``,
        so the model keeps its compile-manager token — every cached
        executable still matches (same abstract shapes) and the rollback
        pays zero recompiles. When the model lives on a MeshLayout the
        loaded leaves are re-placed on its shardings. Verifies integrity
        first (corrupt → quarantine, and with ``fallback`` — the default
        when no version is pinned — the next good version loads instead).
        Returns the version actually loaded.
        """
        from ..utils.serialization import _load_leaves  # noqa: PLC0415

        if fallback is None:
            fallback = version is None
        version, path = self._open_verified(version, fallback=fallback)
        model.init()
        with zipfile.ZipFile(path, "r") as zf:
            meta = json.loads(zf.read("meta.json"))
            params = _load_leaves(zf, "coefficients.npz", model.params)
            opt_state = _load_leaves(zf, "updaterState.npz", model.opt_state)
            state = _load_leaves(zf, "state.npz", model.state)
        layout = getattr(model, "_mesh_layout", None)
        if layout is not None and layout.mesh is not None:
            params = layout.put_params(params)
            opt_state = layout.put_opt_state(opt_state)
            state = layout.put_replicated(state)
        model.params = params
        model.opt_state = opt_state
        model.state = state
        model.iteration = int(meta.get("iteration", 0))
        model.epoch = int(meta.get("epoch", 0))
        self._load_rng(model, path)
        self._m_restores.inc()
        self._flight("online_rollback_load", version=version,
                     iteration=model.iteration)
        return version

    @staticmethod
    def _load_rng(model, path: str) -> None:
        """Restore the training rng key when the container carries one
        (older/plain write_model files simply keep the model's key)."""
        import jax.numpy as jnp  # noqa: PLC0415

        try:
            with zipfile.ZipFile(path, "r") as zf:
                with zf.open("rng.npz") as f:
                    data = np.load(io.BytesIO(f.read()))
                stored = data["rng"]
        except KeyError:
            return
        model._rng = jnp.asarray(
            stored.astype(np.asarray(model._rng).dtype))

    # ---------------------------------------------------------------- misc
    @staticmethod
    def _flight(kind: str, **payload: Any) -> None:
        try:
            from ..telemetry.flight_recorder import get_flight_recorder  # noqa: PLC0415

            get_flight_recorder().record(kind, **payload)
        except Exception:  # observability must never fail a checkpoint
            pass
