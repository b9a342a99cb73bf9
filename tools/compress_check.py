#!/usr/bin/env python
"""compress-check — CI gate for the compressed plan stream
(`make compress-check`, ops/plan_codec.py + the streamed engine tiers).

Asserts, on a small |G|>1 symm config over 2 virtual CPU devices:

1. **Round trip** — every (chunk, shard) record of a lossless-tier plan
   decodes (host-side) to exactly the raw arrays the off-tier engine
   holds; the f32 tier decodes within its documented bound.
2. **Measured-error gate** — the lossless compressed apply matches the
   fused apply within 1e-12 relative (measured: exactly 0 — dictionary
   coefficients are f64); the f32 tier within 1e-6.  Recorded per config
   in the printed JSON line.
3. **Uncompressed tier stays bit-identical** — `stream_compress=off`
   (with its bitpacked `rok` satellite) still reproduces fused to the
   bit.
4. **Bytes gate** — encoded plan bytes ≥ 2.5× smaller than the raw plan
   (the ISSUE 8 acceptance ratio), checked both directly and through an
   ``obs_report diff --phases`` leg: `phase_plan_h2d_bytes` DOWN with
   every compute/exchange/accumulate phase metric flat (threshold 0 —
   structural counts must be exactly preserved).
"""

import os
import subprocess
import sys

# platform pins BEFORE any jax import (same discipline as tests/conftest)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main() -> int:
    import argparse
    import json
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spins", type=int, default=18,
                    help="chain length of the gate config (default 18, "
                         "matching stream-check)")
    ap.add_argument("--min-ratio", type=float, default=2.5,
                    help="required raw/encoded plan-bytes ratio on the "
                         "lossless tier (default 2.5 — the ISSUE 8 "
                         "acceptance bound; the gate config measures ~4x)")
    args = ap.parse_args()

    scratch = tempfile.mkdtemp(prefix="dmt_compress_check_")
    os.environ["DMT_ARTIFACT_CACHE"] = "off"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.utils.config import update_config

    ns = args.spins
    basis = SpinBasis(number_spins=ns, hamming_weight=ns // 2,
                      spin_inversion=1,
                      symmetries=[([*range(1, ns), 0], 0),
                                  ([*reversed(range(ns))], 0)])
    op = heisenberg_from_edges(basis, chain_edges(ns))
    basis.build()
    n = basis.number_states
    print(f"[compress-check] chain_{ns}_symm: N={n}, 2 shards")

    rng = np.random.default_rng(23)
    x = rng.standard_normal(n)
    x /= np.linalg.norm(x)

    eng_f = DistributedEngine(op, n_devices=2, mode="fused")
    yf = np.asarray(eng_f.matvec(eng_f.to_hashed(x)))
    scale = float(np.max(np.abs(yf)))

    def stream_engine(tier):
        update_config(stream_compress=tier)
        try:
            return DistributedEngine(op, n_devices=2, mode="streamed")
        finally:
            update_config(stream_compress="off")

    # -- 3. off tier (bitpacked rok) stays bit-identical to fused ----------
    eng_off = DistributedEngine(op, n_devices=2, mode="streamed")
    y_off = np.asarray(eng_off.matvec(eng_off.to_hashed(x)))
    assert np.array_equal(y_off, yf), "off tier lost bit-identity to fused"
    assert eng_off._plan_chunks[0][0]["rok"].dtype == np.uint32, \
        "off-tier rok is not bitpacked"
    print("[compress-check] off tier: bit-identical to fused, rok packed")

    # -- 1. host round trip: lossless decodes to the off-tier raw arrays ---
    eng_l = stream_engine("lossless")
    assert eng_l._codec.spec["coeff"] == "dict", \
        "symm gate config should dictionary-code"
    off_codec = eng_off._codec
    for ci, per in enumerate(eng_l._plan_chunks):
        for d, enc in per.items():
            dec = eng_l._codec.decode_chunk_host(enc, d)
            raw = off_codec.decode_chunk_host(eng_off._plan_chunks[ci][d],
                                              d)
            ref = eng_l._codec.compact_raw(raw)
            for k in ("dest", "row", "coeff", "ridx", "rok"):
                assert np.array_equal(np.asarray(dec[k]),
                                      np.asarray(ref[k])), (ci, d, k)
    print(f"[compress-check] lossless round trip: exact over "
          f"{len(eng_l._plan_chunks)} chunk(s) (compacted form)")

    # -- 2. measured-error gate --------------------------------------------
    y_l = np.asarray(eng_l.matvec(eng_l.to_hashed(x)))
    err_l = float(np.max(np.abs(y_l - yf)) / scale)
    assert err_l <= 1e-12, f"lossless tier measured error {err_l}"
    eng_32 = stream_engine("f32")
    y_32 = np.asarray(eng_32.matvec(eng_32.to_hashed(x)))
    err_32 = float(np.max(np.abs(y_32 - yf)) / scale)
    assert err_32 <= 1e-6, f"f32 tier measured error {err_32}"
    print(f"[compress-check] measured-error gate: lossless {err_l:.1e} "
          f"(<= 1e-12), f32 {err_32:.1e} (<= 1e-6)")

    # -- 4. bytes gate ------------------------------------------------------
    ratio = eng_l.plan_bytes_raw / eng_l.plan_bytes
    assert ratio >= args.min_ratio, \
        f"compression ratio {ratio:.2f} < {args.min_ratio}"
    print(f"[compress-check] plan bytes {eng_l.plan_bytes_raw} -> "
          f"{eng_l.plan_bytes} ({ratio:.2f}x >= {args.min_ratio}x)")

    # obs_report diff --phases: H2D bytes DOWN, compute/exchange/
    # accumulate structural counts exactly flat.  Both engines emitted
    # apply_phases events above; turn the latest per tier into
    # ``{config: {metric: value}}`` rows, the JSON form ``diff`` reads.
    from distributed_matvec_tpu import obs

    pev = [e for e in obs.events("apply_phases")
           if e.get("engine") == "distributed" and e.get("mode") == "streamed"]
    assert len(pev) >= 2, "missing apply_phases events"

    def phase_row(ev):
        row = {"config": "compress_gate"}
        for p, rec in ev["phases"].items():
            for fld in ("bytes", "gathers", "flops"):
                if rec.get(fld):
                    row[f"phase_{p}_{fld}"] = int(rec[fld])
        return row

    # events arrive in apply order: off's first apply, then lossless's
    base_row, new_row = phase_row(pev[0]), phase_row(pev[1])
    assert new_row["phase_plan_h2d_bytes"] * args.min_ratio \
        <= base_row["phase_plan_h2d_bytes"], \
        (base_row["phase_plan_h2d_bytes"], new_row["phase_plan_h2d_bytes"])
    for k in base_row:
        if k.startswith("phase_") and "plan_h2d" not in k:
            # flat-or-better: dead-entry compaction and the capacity trim
            # legitimately SHRINK compute/exchange/accumulate — only
            # growth would be a regression
            assert new_row.get(k, 0) <= base_row[k], (k, "phase grew")
    base_j = os.path.join(scratch, "phases_off.json")
    new_j = os.path.join(scratch, "phases_lossless.json")
    for path, row in ((base_j, base_row), (new_j, new_row)):
        with open(path, "w") as f:
            json.dump({"compress_gate": row}, f)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
         "diff", base_j, new_j, "--config", "compress_gate",
         "--phases", "--threshold", "0.0"])
    assert r.returncode == 0, "obs_report diff --phases gated a regression"
    print("[compress-check] obs_report diff --phases: plan_h2d bytes "
          f"down {base_row['phase_plan_h2d_bytes']} -> "
          f"{new_row['phase_plan_h2d_bytes']}, compute flat")

    print(json.dumps({"config": f"chain_{ns}_symm",
                      "compress_ratio": round(ratio, 3),
                      "plan_bytes_raw": int(eng_l.plan_bytes_raw),
                      "plan_bytes_encoded": int(eng_l.plan_bytes),
                      "rel_err_lossless": err_l,
                      "rel_err_f32": err_32}))
    print("[compress-check] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
