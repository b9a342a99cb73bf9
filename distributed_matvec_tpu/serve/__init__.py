"""serve — the solve service: a job queue + scheduler multiplexing many
concurrent diagonalize requests over warm engines (DESIGN.md §26).

The production traffic shape is many small-to-medium solves, not one
giant one.  This package is the first layer whose unit of work is a
*job stream*: specs (:mod:`~.spec`) enter a queue (:mod:`~.queue`),
admission is priced by the calibrated capacity model
(``tools/capacity.price_job``), compatible jobs are grouped by engine
fingerprint and batched through ``lanczos_block``'s multi-RHS path with
per-job convergence targets (:mod:`~.scheduler`), engines stay warm in
an LRU byte-budgeted pool (:mod:`~.pool`), and the whole loop runs as a
preemption-safe service (:mod:`~.service`).

Quickstart::

    from distributed_matvec_tpu.serve import (JobSpec, JobQueue,
                                              EnginePool, Scheduler)
    sched = Scheduler()
    sched.submit(JobSpec(job_id="j0", basis={"number_spins": 12,
                                             "hamming_weight": 6}))
    sched.drain()
    sched.queue.result("j0")["eigenvalues"]

A scripted burst of mixed jobs is ``make serve-check``
(``tools/serve_check.py``); run a spool-backed service with ``python apps/solve_service.py DIR``; submit from the CLI
with ``python apps/diagonalize.py model.yaml --submit --serve-dir DIR``.
"""

from .pool import EnginePool, build_engine, build_operator, engine_bytes
from .queue import (DONE, FAILED, QUEUED, REJECTED, RUNNING, JobQueue,
                    submit_to_spool)
from .scheduler import Scheduler, load_capacity_module
from .service import SolveService
from .spec import JobSpec, estimate_dimension

__all__ = [
    "JobSpec", "estimate_dimension",
    "JobQueue", "submit_to_spool",
    "QUEUED", "RUNNING", "DONE", "FAILED", "REJECTED",
    "EnginePool", "build_engine", "build_operator", "engine_bytes",
    "Scheduler", "load_capacity_module",
    "SolveService",
]
