"""Content-addressed on-disk artifact cache — default ON.

Constructing an engine costs far more than applying it once, yet each of
the two expensive construction products is a pure function of content that
rarely changes:

  basis/       representative + norm arrays, keyed by the basis JSON
               (sector, symmetries, particle content) — the
               ``makeBasisStates`` restore of Diagonalize.chpl:227-246,
               now automatic instead of opt-in;
  structure/   ELL/compact structure sidecars, keyed by the engines'
               ``_structure_fingerprint()`` (basis content + operator term
               tables + mode/dtype/padding);

The persistent XLA compilation cache is NOT part of this tree: it follows
``JAX_COMPILATION_CACHE_DIR`` or sits inside the checkout (utils/cache.py).

Two cheaper-but-still-cacheable decision products ride in the same tree:

  calibration/ measured hardware rates (obs/roofline.py sidecars);
  tuning/      autotuner decisions (tune/search.py: the chosen knob
               config per (structure, rates, mode) fingerprint) and the
               live rate posteriors (tune/live.py, ``*.posterior.json``)
               that capacity planning and serve admission price from.

All of it lives under one root (first hit wins):

  ``DMT_ARTIFACT_DIR`` env var > ``artifact_dir`` config field >
  ``~/.cache/distributed_matvec_tpu/artifacts``

and the whole layer is switched by the ``artifact_cache`` config knob
(``DMT_ARTIFACT_CACHE=off`` to disable).  Engines consult this layer only
when the caller did not pass an explicit ``structure_cache`` path; explicit
paths keep their exact previous semantics (including loud save errors),
while default-path saves fail soft — a read-only checkout must never turn
a cache write into an engine-construction error.

This is the GSPMD-style separation of one-time partitioning/compilation
cost from steady-state throughput (arXiv:2105.04663): the build is paid
once per *content*, not once per process.
"""

from __future__ import annotations

import os
from typing import Optional

from .config import get_config
from .logging import log_debug, log_warn

__all__ = [
    "artifact_root",
    "artifacts_enabled",
    "artifact_path",
    "default_structure_cache",
    "basis_fingerprint",
    "soft_save_structure",
    "make_or_restore_basis",
    "ensure_compilation_cache",
    "within_size_cap",
    "record_cache_event",
    "note_artifact_corrupt",
    "quarantine_artifact",
]


def record_cache_event(kind: str, event: str) -> None:
    """One artifact-cache outcome into the metrics registry
    (``artifact_cache{kind=basis|structure|tuning,
    event=hit|miss|save|evict}``)
    — the single call site engines and this module share, so the report
    tooling's hit-rate math cannot drift from the recording."""
    from ..obs.metrics import counter

    counter("artifact_cache", kind=kind, event=event).inc()

_DEFAULT_SUBDIR = os.path.join(".cache", "distributed_matvec_tpu", "artifacts")

# per-path corrupt-read tally for the retry/quarantine policy (DESIGN.md
# §21): one failure is counted (transient disks happen), a second moves
# the file out of the cache's way
_read_failures: dict = {}


def note_artifact_ok(path: str) -> None:
    """Clear the corruption tally for ``path`` — called by the atomic
    save paths after a successful write, so a rebuilt-and-re-saved
    artifact starts with a clean record (one later transient failure must
    not quarantine a healed file)."""
    _read_failures.pop(path, None)


def note_artifact_corrupt(path: str, kind: str, error=None) -> bool:
    """Record a corrupt/unreadable artifact read and apply the quarantine
    policy: every failure bumps ``artifact_cache{kind=...,event=corrupt}``
    and emits an ``artifact_cache`` corrupt event; the SECOND failure on
    the same path moves the file into a ``.quarantine/`` sibling directory
    (:func:`quarantine_artifact`) so the cache stops serving it — the
    caller's rebuild-from-structure fallback then becomes permanent for
    that entry instead of retrying a bad file forever.  Returns True when
    the file was quarantined."""
    record_cache_event(kind, "corrupt")
    try:
        from ..obs.events import emit

        # NB: "kind" is an envelope key — the artifact kind rides as
        # artifact_kind (same convention as the counter's labels)
        emit("artifact_cache", artifact_kind=kind, event="corrupt",
             path=path, error=repr(error))
    except Exception:
        pass
    n = _read_failures.get(path, 0) + 1
    _read_failures[path] = n
    if n < 2:
        log_warn(f"corrupt {kind} artifact {path} ({error!r}); rebuilding "
                 "— a second failure will quarantine the file")
        return False
    return quarantine_artifact(path, kind, reason=repr(error))


def quarantine_artifact(path: str, kind: str, reason: str = "") -> bool:
    """Move a bad artifact into ``.quarantine/`` next to it (same
    filesystem, atomic rename) and emit an ``artifact_quarantine`` event.
    Fails soft: an unmovable file logs one warning and stays — readers
    already treat it as a miss."""
    if not os.path.exists(path):
        return False
    qdir = os.path.join(os.path.dirname(os.path.abspath(path)),
                        ".quarantine")
    try:
        os.makedirs(qdir, exist_ok=True)
        dest = os.path.join(qdir, os.path.basename(path))
        i = 1
        while os.path.exists(dest):
            dest = os.path.join(qdir, f"{os.path.basename(path)}.{i}")
            i += 1
        os.replace(path, dest)
    except OSError as e:
        log_warn(f"quarantine of {path} failed: {e!r}")
        return False
    _read_failures.pop(path, None)
    record_cache_event(kind, "quarantine")
    try:
        from ..obs.events import emit

        emit("artifact_quarantine", artifact_kind=kind, path=path,
             moved_to=dest, reason=reason)
    except Exception:
        pass
    try:
        # a quarantine means a cache is actively serving corrupt bytes —
        # bundle the context (what was being read, by which span) so the
        # post-mortem names the artifact even if the run later dies
        from ..obs.flight import flight_dump

        flight_dump("quarantine", artifact_kind=kind, path=path,
                    moved_to=dest, error=reason)
    except Exception:
        pass
    log_warn(f"quarantined corrupt {kind} artifact: {path} -> {dest}")
    return True


def artifacts_enabled() -> bool:
    """Whether the default-on artifact layer is active.

    The env var is consulted directly (not just through the config
    snapshot) so a harness can flip it for a subprocess without racing
    the config cache."""
    env = os.environ.get("DMT_ARTIFACT_CACHE")
    knob = env if env is not None else get_config().artifact_cache
    knob = str(knob).strip().lower()
    if knob in ("on", "1", "true", "yes", ""):
        return True
    if knob not in ("off", "0", "false", "no"):
        # fail SOFT and closed: this runs inside every engine construction,
        # so an unrecognized value (typo for "off", most likely) must not
        # crash the engine — and silently caching when the user tried to
        # disable would be the surprising direction
        import warnings

        warnings.warn(f"unknown artifact_cache setting {knob!r} "
                      "(use on | off); treating as off", stacklevel=2)
    return False


def artifact_root() -> str:
    """Resolve the artifact root directory (no filesystem side effects).

    Raises ``OSError`` when no root is configured and there is no home
    directory to default to — every caller already treats an ``OSError``
    from this layer as "cache unavailable" and builds instead."""
    root = os.environ.get("DMT_ARTIFACT_DIR") or get_config().artifact_dir
    if root:
        return root
    home = os.path.expanduser("~")
    if not os.path.isabs(home):
        # HOME unset and no passwd entry: "~" comes back unexpanded, and
        # joining it would quietly create a "~" directory under the cwd
        raise OSError("no home directory for the default artifact root; "
                      "set DMT_ARTIFACT_DIR")
    return os.path.join(home, _DEFAULT_SUBDIR)


def artifact_path(kind: str, fingerprint: str, suffix: str = "") -> str:
    """``root/<kind>/<fp[:2]>/<fp><suffix>`` with the directory created.

    The two-hex-char shard keeps any one directory from accumulating an
    unbounded flat listing on long-lived caches."""
    d = os.path.join(artifact_root(), kind, fingerprint[:2])
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, fingerprint + suffix)


def default_structure_cache(fingerprint: str) -> Optional[str]:
    """Content-addressed base path for an engine structure sidecar, or
    ``None`` when the layer is off (or the root is uncreatable — a broken
    cache disk must degrade to a plain rebuild, not an engine error)."""
    if not artifacts_enabled():
        return None
    try:
        return artifact_path("structure", fingerprint)
    except OSError as e:
        log_debug(f"artifact cache unavailable: {e!r}")
        return None


def within_size_cap(nbytes: int) -> bool:
    """Whether a DEFAULT-path structure sidecar of ``nbytes`` may be written
    (the ``artifact_max_gb`` knob; explicit paths are never capped)."""
    return nbytes <= get_config().artifact_max_gb * 1e9


def soft_save_structure(sidecar: str, fingerprint: str, mode: str,
                        payload: dict) -> bool:
    """DEFAULT-path (artifact cache) structure/plan sidecar save: honors
    the ``artifact_max_gb`` size cap and degrades to a debug log on I/O
    errors — a read-only checkout or full cache disk must never turn a
    cache write into an engine-construction error.  True when written."""
    from ..io.hdf5 import save_engine_structure

    nbytes = sum(getattr(v, "nbytes", 0) for v in payload.values())
    if not within_size_cap(nbytes):
        record_cache_event("structure", "evict")
        log_debug(f"structure artifact save skipped: {nbytes/1e9:.1f} GB "
                  "exceeds artifact_max_gb")
        return False
    try:
        save_engine_structure(sidecar, fingerprint, mode, payload)
    except OSError as e:
        log_warn(f"structure artifact save skipped: {e!r}")
        return False
    record_cache_event("structure", "save")
    return True


def basis_fingerprint(basis) -> str:
    """Identity of a basis *definition* (not its enumerated output): the
    JSON dict that also seeds the engines' structure fingerprints."""
    import hashlib
    import json

    h = hashlib.sha256()
    h.update(json.dumps(basis._json_dict(), sort_keys=True,
                        default=str).encode())
    h.update(b"|basis-v1")
    return h.hexdigest()


def make_or_restore_basis(basis, path: Optional[str] = None,
                          save: bool = True) -> bool:
    """Build ``basis``, restoring representatives from the artifact cache
    when a matching checkpoint exists (True = restored).

    ``path=None`` resolves the content-addressed default; an explicit path
    keeps :func:`~..io.hdf5.make_or_restore_representatives` semantics.
    Restores use the existing loader; saves go through an atomic
    temp-file + ``os.replace`` so concurrent processes warming the same
    basis can never interleave partial writes (only process 0 of a
    multi-controller run writes at all).  Everything fails soft: with the
    layer off, h5py missing, or the cache dir unwritable this is exactly
    ``basis.build()``.
    """
    if basis.is_built:
        return False
    if path is None:
        if not artifacts_enabled():
            basis.build()
            return False
        try:
            path = artifact_path("basis", basis_fingerprint(basis), ".h5")
        except OSError as e:
            log_debug(f"artifact cache unavailable: {e!r}")
            basis.build()
            return False
    try:
        from ..io.hdf5 import load_basis, save_basis
    except Exception as e:  # pragma: no cover - h5py always present in CI
        log_debug(f"basis artifact cache disabled (no HDF5 I/O): {e!r}")
        basis.build()
        return False
    from . import faults

    def _load():
        if os.path.exists(path):
            faults.check("artifact_read", path=path)
        return load_basis(path)

    try:
        # bounded retry: a transient read blip must not cost a rebuild;
        # a persistently corrupt checkpoint falls through to the rebuild
        # path AND the corrupt/quarantine tally
        got = faults.with_retries("artifact_read", _load)
    except OSError as e:
        got = None          # truncated/corrupt checkpoint: rebuild
        note_artifact_corrupt(path, "basis", e)
    if got is not None and got[1] is not None:
        reps, norms = got
        basis.unchecked_set_representatives(reps, norms)
        record_cache_event("basis", "hit")
        log_debug(f"basis representatives restored from {path}")
        return True
    record_cache_event("basis", "miss")
    basis.build()
    if not save:
        return False
    try:
        import jax

        if jax.process_count() > 1 and jax.process_index() != 0:
            return False
    except Exception:
        pass
    try:
        import tempfile

        faults.check("artifact_save", path=path)
        d = os.path.dirname(os.path.abspath(path))
        fd, tmp = tempfile.mkstemp(suffix=".h5.tmp", dir=d)
        os.close(fd)
        os.chmod(tmp, 0o644)
        try:
            save_basis(tmp, basis.representatives, basis.norms)
            os.replace(tmp, path)
            note_artifact_ok(path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        record_cache_event("basis", "save")
        log_debug(f"basis representatives checkpointed to {path}")
    except OSError as e:
        log_warn(f"basis artifact save skipped: {e!r}")
    return False


def ensure_compilation_cache() -> Optional[str]:
    """Engines call this at construction: the persistent compilation cache
    is on unless the artifact layer is off (``DMT_ARTIFACT_CACHE=off`` keeps
    a run free of every cache).  Where it lives is
    :func:`~.cache.enable_compilation_cache`'s rule, not the artifact
    root's."""
    if not artifacts_enabled():
        return None
    from .cache import enable_compilation_cache

    return enable_compilation_cache()
