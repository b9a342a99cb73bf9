"""The program's HBM watermarks, as the per-layer metrics under
``peak_hbm_gb`` read them (PR 37).

``distributed_matvec_tpu/obs/memory.py::sample_watermark`` emits one
``memory_watermark`` event a sample, among this run's copy of the program's
events (``run.events``, see ``program_spans``).  Of a sample the readers use

* ``fullest``: ``memory_stats()`` of the local device with the largest
  ``peak_bytes_in_use`` (``bytes_in_use``, ``peak_bytes_in_use``): the device
  that the harness's own ``memory_peak_bytes`` is read on, so that a sample
  can be subtracted from it;
* ``ledger``: what the program's memory ledger held on that device when the
  sample was taken, by owner (``engine``: levels, ``pos_of``, lookup, basis
  rows, diagonal, operator tables; ``solver``: the Krylov buffer; ``plan``:
  a plan build's staging), and ``ledger_bytes``, their sum;
* ``synced``: the program had just waited for the device, so ``bytes_in_use``
  is what is resident with nothing in flight;
* ``tag``, and the envelope's ``span_id``: the span the sample was taken in,
  joined here to the span's name.

Two samples matter.  The built engine's (``tag`` ``engine_init/<kind>``, the
last thing an engine's ``__init__`` does, after it has waited for the
device): the engine resident, the build's temporaries gone, the peak the
highest the build reached.  And, in a solve, the one at the close of each
``lanczos/wait`` span: what stays on the chip between two block programs.

Every function returns ``None`` where the events carry no such sample (a
program from before PR 37, whose samples lack ``fullest``; a CPU rehearsal,
whose backend has no ``memory_stats()``), and raises where the program's
ring has dropped events of this run and the sample is not found.  All bytes
are one device's.
"""

from . import program_spans

KIND = "memory_watermark"
WAIT = "lanczos/wait"


def samples(run, part):
    """The samples of this run's ``build`` or ``window`` that name their
    fullest device, oldest first."""
    return [e for e in program_spans.run_events(run)[part]
            if e.get("kind") == KIND and e.get("fullest")]


def _lost(run, what):
    if program_spans.run_events(run)["lost"]:
        raise RuntimeError(
            f"no {what} among this run's events, and the program's ring "
            "has dropped some of them: it may have been among those")


def built(run):
    """The sample of the engine this run's set-up built."""
    tag = "engine_init/" + run.config["engine"]["kind"]
    found = [e for e in samples(run, "build") if e.get("tag") == tag]
    if not found:
        _lost(run, f"{tag} sample")
        return None
    if len(found) != 1:
        raise RuntimeError(
            f"{len(found)} {tag} samples were emitted while this run's "
            "engine was built: which engine is the run's?")
    return found[0]


def between_programs(run):
    """The window's synced samples taken in a ``lanczos/wait`` span: one a
    block program, right after the host has waited for it."""
    waits = {e.get("span_id") for e in program_spans.span_events(run, "window")
             if e.get("name") == WAIT}
    found = [e for e in samples(run, "window")
             if e.get("synced") and e.get("span_id") in waits]
    if not found:
        _lost(run, f"synced {WAIT} sample")
    return found


def resident(run):
    """The sample that shows the most resident between two block programs
    (``fullest.bytes_in_use``); ``None`` where the window has none."""
    found = between_programs(run)
    if not found:
        return None
    return max(found, key=lambda e: e["fullest"]["bytes_in_use"])


def peak_bytes(run):
    """``peak_bytes_in_use`` of the fullest device when the window closed:
    the bytes of ``peak_hbm_gb``."""
    return int(run.device["memory_peak_bytes"])
