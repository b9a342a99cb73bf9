#!/usr/bin/env python
"""hybrid-check — CI gate for the per-term recompute-vs-stream split
(`make hybrid-check`, DESIGN.md §28, the `hybrid` engine mode).

Asserts, on 2 virtual CPU devices with the artifact cache OFF (so the
rate calibration resolves to the documented defaults and every priced
verdict below is deterministic on any machine):

1. **Degenerate splits equal the existing modes** — `all-stream` is the
   streamed engine (bit-identical apply, byte-identical plan size at the
   same tier) and `all-recompute` reproduces the same apply bit-for-bit
   while storing NO per-term plan slices (only the shared receive
   layout), on a |G|=64 symm chain AND a |G|=1 transverse-field ring.
2. **Mixed split bit-identity at every pipeline depth** — a pinned
   `stream:<terms>` split (field terms recomputed, XY bonds streamed)
   equals the pure-streamed apply bit-for-bit at depth 0 AND depth 2
   (multi-chunk plans, single vector and a k=3 batch), with the
   structural overflow/invalid counters preserved and plan bytes
   strictly below the same-tier streamed plan.
3. **The auto split prices deterministically** — under the default CPU
   rates the symm config (|G|=64 orbit scans) resolves all-stream and
   the |G|=1 field config all-recompute, both bit-identical; a
   single-chunk hybrid plan resolves `pipeline_depth=auto` to
   sequential exactly like streamed (the PR 10 contract).
4. **Plan bytes below streamed via `obs_report diff --phases`** — the
   hybrid leg's `phase_plan_h2d_bytes` is DOWN against the same-tier
   streamed baseline while the merged exchange/accumulate structural
   counts stay EXACTLY equal (threshold 0 — the §28 merged-slot
   argument made machine-checkable).
5. **Offline pricer reaches a genuine mix** — the shared
   `price_term_split` model under the documented TPU rates puts a
   |G|=48 sector's term spread on BOTH sides of the split
   (`tools/capacity.py --hybrid`'s table), `recommend` points at
   `hybrid` with the priced split when it beats both pure tiers, and
   `price_job` prices a hybrid-mode spec.
"""

import os
import subprocess
import sys

# platform pins BEFORE any jax import (same discipline as the siblings)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
for var in ("DMT_HYBRID", "DMT_PIPELINE", "DMT_STREAM_COMPRESS",
            "DMT_OBS", "DMT_OBS_DIR", "DMT_FAULT", "DMT_PHASES"):
    os.environ.pop(var, None)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))


def main() -> int:
    import json
    import tempfile

    scratch = tempfile.mkdtemp(prefix="dmt_hybrid_check_")
    # cache OFF: fresh builds (no sidecar restores) AND no measured
    # calibration sidecar — the auto split prices at the documented
    # default rates, so step 3's verdicts are machine-independent
    os.environ["DMT_ARTIFACT_CACHE"] = "off"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.models.operator import Operator
    from distributed_matvec_tpu.obs import roofline as R
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.utils.config import update_config

    rng = np.random.default_rng(31)

    # -- the two gate configs ----------------------------------------------
    ns = 16
    sb = SpinBasis(number_spins=ns, hamming_weight=ns // 2,
                   spin_inversion=1,
                   symmetries=[([*range(1, ns), 0], 0),
                               ([*reversed(range(ns))], 0)])
    op_symm = heisenberg_from_edges(sb, chain_edges(ns))
    sb.build()

    nf = 12
    fb = SpinBasis(number_spins=nf)
    op_field = Operator.from_expressions(
        fb,
        [("-1.0 × σᶻ₀ σᶻ₁", [list(e) for e in chain_edges(nf)]),
         ("0.75 × σˣ₀", [[i] for i in range(nf)]),
         ("0.25 × σˣ₀ σˣ₁ + 0.25 × σʸ₀ σʸ₁",
          [[i, (i + nf // 2) % nf] for i in range(0, nf, 4)])],
        name="tfxy gate ring")
    fb.build()
    # the field config's pinned MIXED split: stream the two-site XY
    # bonds, recompute the single-site fields
    pair_split = "stream:" + ",".join(
        map(str, op_field.off_diag_table.term_indices_by_flip_weight(2)))
    print(f"[hybrid-check] chain_{ns}_symm N={sb.number_states}, "
          f"tfxy_{nf} N={fb.number_states} (mixed split {pair_split})")

    update_config(stream_compress="lossless")

    def engine(op, mode, split=None, depth=0, batch=None):
        kw = dict(n_devices=2, mode=mode, pipeline_depth=depth,
                  batch_size=batch or 64)
        if split is not None:
            kw["hybrid_split"] = split
        return DistributedEngine(op, **kw)

    # -- 1. + 3. degenerate and auto splits --------------------------------
    for name, op, x in (
            (f"chain_{ns}_symm", op_symm,
             rng.standard_normal(sb.number_states)),
            (f"tfxy_{nf}", op_field,
             rng.standard_normal(fb.number_states))):
        es = engine(op, "streamed")
        ys = np.asarray(es.matvec(es.to_hashed(x)))
        for split in ("all-stream", "all-recompute", "auto"):
            eh = engine(op, "hybrid", split)
            yh = np.asarray(eh.matvec(eh.to_hashed(x)))
            assert np.array_equal(ys, yh), \
                f"{name} hybrid {split} lost bit-identity to streamed"
            if split == "all-stream":
                assert eh.plan_bytes == es.plan_bytes, \
                    (name, eh.plan_bytes, es.plan_bytes)
            elif split == "all-recompute":
                assert eh.plan_bytes < es.plan_bytes, \
                    (name, eh.plan_bytes, es.plan_bytes)
            else:
                # deterministic priced verdicts under the default rates:
                # |G|=64 orbit scans are never cheaper than streaming,
                # |G|=1 single-flip scans always are (default gather is
                # the bound) — DESIGN.md §28's worked break-even
                want = 1.0 if op is op_symm else 0.0
                assert eh.hybrid_stream_fraction == want, \
                    (name, eh.hybrid_stream_fraction, want)
        print(f"[hybrid-check] {name}: all-stream == streamed "
              "(bytes equal), all-recompute bit-identical (bytes below "
              f"streamed's {es.plan_bytes}), auto priced "
              f"{'all-stream' if op is op_symm else 'all-recompute'}")

    # single-chunk hybrid plan resolves pipeline auto to sequential (the
    # PR 10 choose_pipeline_depth contract extends to the new mode)
    e1 = DistributedEngine(op_symm, n_devices=2, mode="hybrid",
                           hybrid_split="all-stream",
                           pipeline_depth="auto", batch_size=4096)
    assert e1._plan_nchunks_v == 1 and e1.pipeline_depth == 0, \
        (e1._plan_nchunks_v, e1.pipeline_depth)
    print("[hybrid-check] single-chunk hybrid plan: pipeline auto "
          "resolves sequential")

    # -- 2. mixed split at pipeline depths {0, 2} --------------------------
    x = rng.standard_normal(fb.number_states)
    X3 = rng.standard_normal((fb.number_states, 3))
    es = engine(op_field, "streamed", batch=256)
    eh0 = engine(op_field, "hybrid", pair_split, depth=0, batch=256)
    eh2 = engine(op_field, "hybrid", pair_split, depth=2, batch=256)
    assert eh0._plan_nchunks_v >= 2, eh0._plan_nchunks_v
    assert eh2.pipeline_depth == 2, eh2.pipeline_depth
    assert 0.0 < eh0.hybrid_stream_fraction < 1.0
    assert eh0.plan_bytes < es.plan_bytes, (eh0.plan_bytes, es.plan_bytes)
    for xv in (x, X3):
        ys = np.asarray(es.matvec(es.to_hashed(xv)))
        y0 = np.asarray(eh0.matvec(eh0.to_hashed(xv)))
        y2 = np.asarray(eh2.matvec(eh2.to_hashed(xv)))
        assert np.array_equal(ys, y0), "mixed split depth 0 not identical"
        assert np.array_equal(ys, y2), "mixed split depth 2 not identical"
    assert eh0._stream_overflow == es._stream_overflow
    assert eh0._stream_invalid == es._stream_invalid
    print(f"[hybrid-check] mixed split {pair_split}: bit-identical to "
          f"streamed at depths 0 and 2 (single + k=3), plan "
          f"{eh0.plan_bytes} < {es.plan_bytes} B "
          f"({1 - eh0.plan_bytes / es.plan_bytes:.0%} smaller)")

    # -- 4. plan-bytes-below-streamed via obs_report diff --phases ---------
    pev = [e for e in obs.events("apply_phases")
           if e.get("engine") == "distributed"]
    s_ev = [e for e in pev if e.get("mode") == "streamed"][-1]
    h_ev = [e for e in pev if e.get("mode") == "hybrid"][-1]

    def phase_row(ev):
        row = {"config": "hybrid_gate"}
        for p, rec in ev["phases"].items():
            for fld in ("bytes", "gathers", "flops"):
                if rec.get(fld):
                    row[f"phase_{p}_{fld}"] = int(rec[fld])
        return row

    base_row, new_row = phase_row(s_ev), phase_row(h_ev)
    assert new_row["phase_plan_h2d_bytes"] < base_row["phase_plan_h2d_bytes"]
    for p in ("exchange", "accumulate"):
        for fld in ("bytes", "gathers"):
            k = f"phase_{p}_{fld}"
            assert new_row.get(k) == base_row.get(k), \
                (k, base_row.get(k), new_row.get(k))
    assert new_row.get("phase_compute_recompute_flops", 0) > 0
    base_j = os.path.join(scratch, "phases_streamed.json")
    new_j = os.path.join(scratch, "phases_hybrid.json")
    for path, row in ((base_j, base_row), (new_j, new_row)):
        with open(path, "w") as f:
            json.dump({"hybrid_gate": row}, f)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
         "diff", base_j, new_j, "--config", "hybrid_gate",
         "--phases", "--threshold", "0.0"])
    assert r.returncode == 0, "obs_report diff --phases gated a regression"
    print(f"[hybrid-check] diff --phases: plan_h2d "
          f"{base_row['phase_plan_h2d_bytes']} -> "
          f"{new_row['phase_plan_h2d_bytes']} B, exchange/accumulate "
          "exactly flat, recompute flops attributed")

    # -- 5. offline pricer: a genuine mix under the documented TPU rates ---
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import capacity

    tpu = R.default_calibration("tpu")
    # |G|=48 puts the per-term break-even INSIDE the modeled live
    # spread: a genuinely mixed priced split
    hyb = capacity.hybrid_split_model(
        n_states=1_000_000, num_terms=24, pair=False, n_devices=8,
        group_order=48, rates=tpu, eff_tier="lossless")
    assert hyb and 0 < hyb["stream_terms"] < hyb["num_terms"], \
        (hyb or {}).get("stream_terms")
    # |G|=16 on the off tier: the recompute credit (plus the forced
    # compaction) is decisive and the recommendation flips to hybrid
    report = capacity.plan(10_000_000_000, 24, 24, False, 16.0, 64,
                           1, 2, rates=tpu, group_order=16)
    hyb_est = report["modes"]["hybrid"]["est_apply_ms"]
    str_est = report["modes"]["streamed"]["est_apply_ms"]
    fus_est = report["modes"]["fused"]["est_apply_ms"]
    assert hyb_est < str_est and hyb_est < fus_est, \
        (hyb_est, str_est, fus_est)
    rec = capacity.recommend(report, 10_000_000_000)
    assert rec["recommended_mode"] == "hybrid", rec["recommended_mode"]
    assert rec.get("recommended_hybrid_split") == "auto", rec
    priced = capacity.price_job(
        {"n_states": 10_000_000_000, "num_terms": 24, "t0": 24,
         "pair": False, "n_devices": 64, "mode": "hybrid", "k": 2,
         "group_order": 16}, calibration=tpu)
    assert priced["fits"] and priced["est_apply_ms"], priced
    print(f"[hybrid-check] offline pricer (TPU rates): |G|=48 splits "
          f"{hyb['stream_terms']}/{hyb['num_terms']} terms streamed; "
          f"|G|=16 recommend -> {rec['recommended_mode']} "
          f"({hyb_est:.0f} < streamed {str_est:.0f} / fused "
          f"{fus_est:.0f} ms), price_job est "
          f"{priced['est_apply_ms']} ms/apply")

    print("[hybrid-check] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
