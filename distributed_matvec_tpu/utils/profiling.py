"""Device-level profiling hooks.

The reference carries Chapel ``CommDiagnostics``/``VisualDebug`` hooks behind
``kVerboseComm`` (``DistributedMatrixVector.chpl:19``, ``v1/basis.chpl:7``);
the TPU-native analog is a ``jax.profiler`` trace (viewable in TensorBoard /
Perfetto) gated by the ``profile_dir`` config field (``DMT_PROFILE_DIR=…``).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from .config import get_config

__all__ = ["maybe_profile"]


@contextmanager
def maybe_profile(create_perfetto_link: bool = False,
                  profile_dir: Optional[str] = None):
    """Trace the enclosed block when a profile directory is set; otherwise
    a no-op.  Usage::

        with maybe_profile():
            y = eng.matvec(x)

    ``profile_dir`` overrides the global ``config.profile_dir`` field for
    this one block — a harness can profile exactly one apply per
    config into its own directory without mutating process-global config or
    env vars.  An explicit empty string forces the no-op regardless of the
    config field; ``None`` (default) defers to the config.

    Part of the continuous-profiling plane (obs/profile.py): a captured
    trace directory is stamped with ``PROFILE_META.json``
    (trace_id/job_id) and announced by a ``profile_captured`` event, so
    manual profiles are discoverable from the event stream instead of
    being orphan directories.
    """
    d = profile_dir if profile_dir is not None else get_config().profile_dir
    if not d:
        yield
        return
    import jax

    with jax.profiler.trace(d, create_perfetto_link=create_perfetto_link):
        yield
    # stamp + announce AFTER the trace closes (its files exist now);
    # soft-fail — a broken obs layer must not break the profiled block
    try:
        from ..obs.events import emit, obs_enabled
        from ..obs.profile import stamp_profile_dir

        if obs_enabled():
            stamp_profile_dir(d, capture="manual")
            emit("profile_captured", capture="manual", dir=d)
    except Exception:
        pass
