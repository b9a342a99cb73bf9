#!/usr/bin/env python
"""pipeline-check — CI gate for pipelined applies (`make pipeline-check`).

Asserts, on the CPU rig:

1. **Bit-identity** — pipelined applies (DESIGN.md §25) equal sequential
   ones bit-for-bit, fused AND streamed, single vector AND a k=3 batch:
   the staged ``ppermute`` exchange reassembles the monolithic
   ``all_to_all`` layout exactly and exchanges retire in chunk order, so
   no accumulation reorders.  The structural overflow/invalid counters
   are preserved.
2. **Same work, and the measured split is reported** — the pipelined
   streamed applies' `apply_phases` events carry exactly the sequential
   applies' per-phase bytes / gathers / flops (the schedule moves, the
   arithmetic does not) plus the `pipeline` record (depth, barrier_ms,
   hidden_ms, overlap_fraction), and `roofline_report` puts the two
   side by side (`measured_speedup`, `barrier_ms`).

The time-at-barrier cut under an injected straggler on a real 2-process
run is `tests/test_engine_pipelined.py::test_multihost_pipelined_barrier_cut`.
"""

import os
import sys

# platform pins BEFORE any jax import (same discipline as the siblings)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
for var in ("DMT_PIPELINE", "DMT_OBS", "DMT_OBS_DIR", "DMT_FAULT",
            "DMT_PHASES"):
    os.environ.pop(var, None)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main() -> int:
    os.environ["DMT_ARTIFACT_CACHE"] = "off"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.yaml_io import operator_from_dict
    from distributed_matvec_tpu.obs import roofline as R
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    ns = 12
    basis = SpinBasis(number_spins=ns, hamming_weight=ns // 2)
    basis.build()
    op = operator_from_dict({"terms": [{
        "expression": "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁",
        "sites": [[i, (i + 1) % ns] for i in range(ns)]}]}, basis)
    n = basis.number_states
    rng = np.random.default_rng(11)
    x = rng.standard_normal(n)
    X3 = rng.standard_normal((n, 3))
    print(f"[pipeline-check] chain_{ns}: N={n}, 2 shards")

    # -- 1. bit-identity + counters, fused and streamed --------------------
    for mode in ("fused", "streamed"):
        seq = DistributedEngine(op, n_devices=2, mode=mode, batch_size=64,
                                pipeline_depth=0)
        pipe = DistributedEngine(op, n_devices=2, mode=mode, batch_size=64,
                                 pipeline_depth=4)
        assert pipe.pipeline_depth >= 2, pipe.pipeline_depth
        for xv in (x, X3):
            ys = np.asarray(seq.matvec(seq.to_hashed(xv)))
            yp = np.asarray(pipe.matvec(pipe.to_hashed(xv)))
            assert np.array_equal(ys, yp), \
                (f"{mode} pipelined apply is not bit-identical "
                 f"(k={1 if xv.ndim == 1 else xv.shape[1]})")
        if mode == "streamed":
            assert pipe._stream_overflow == seq._stream_overflow
            assert pipe._stream_invalid == seq._stream_invalid
        print(f"[pipeline-check] {mode}: pipelined == sequential "
              "bit-for-bit (single + k=3), counters preserved")

    # -- 2. same counts, and the measured split reported -------------------
    # (batch 128 → a 4-chunk stream: genuinely pipelined)
    seq = DistributedEngine(op, n_devices=2, mode="streamed",
                            batch_size=128, pipeline_depth=0)
    pipe = DistributedEngine(op, n_devices=2, mode="streamed",
                             batch_size=128, pipeline_depth=4)
    xs, xp_ = seq.to_hashed(x), pipe.to_hashed(x)
    obs.reset()
    for eng, xh in ((seq, xs), (pipe, xp_)):
        for _ in range(3):
            yh = eng.matvec(xh)
        jax.block_until_ready(yh)
    evs = obs.events("apply_phases")
    s_ev = [e for e in evs if "pipeline" not in e][-1]
    p_ev = [e for e in evs if "pipeline" in e][-1]

    def counts(ev):
        return {(p, fld): int(rec.get(fld) or 0)
                for p, rec in ev["phases"].items()
                for fld in ("bytes", "gathers", "flops")}

    assert counts(p_ev) == counts(s_ev), (counts(s_ev), counts(p_ev))
    assert p_ev["pipeline"]["depth"] == pipe.pipeline_depth
    for k in ("barrier_ms", "hidden_ms", "overlap_fraction"):
        assert k in p_ev["pipeline"], p_ev["pipeline"]
    report = R.roofline_report(evs, R.default_calibration("cpu"))
    base = report["groups"].get("distributed/streamed")
    pgrp = report["groups"].get("distributed/streamed+pipe4")
    assert base and pgrp, sorted(report["groups"])
    assert pgrp.get("measured_speedup") is not None
    assert pgrp.get("barrier_ms") is not None
    print("[pipeline-check] streamed+pipe4: per-phase bytes/gathers/flops "
          "equal the sequential apply's; pipeline record and roofline "
          "side-by-side present")

    print("[pipeline-check] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
