#!/usr/bin/env python
"""stream-check — CI gate for the streamed engine mode (`make stream-check`).

Asserts, on a small |G|>1 config over 2 virtual CPU devices:

1. **Bit-identity** — the streamed apply reproduces the fused apply
   exactly (same routing, same accumulation order), for single vectors
   and a k=3 batch, and ⟨x, Hx⟩ matches to the bit.
2. **Counters preserved** — after streamed applies the
   ``exchange_overflow`` / ``exchange_invalid`` series exist in the
   metrics registry (zero being the healthy reading), exactly as fused
   mode reports them.
3. **Less work an apply, by count** — the streamed apply's
   ``apply_phases`` event counts no orbit-scan gathers and fewer compute
   flops than the fused apply's (the plan was resolved once, at build),
   moves exactly ``plan_bytes`` host-to-device, and leaves the
   accumulate phase's counts as they were.  Structural counts from the
   shapes: they repeat exactly on any machine; what the apply takes is a
   time and is left to the benchmark's cells.
4. **Pure host-RAM streaming** — the whole main phase runs with
   ``DMT_ARTIFACT_CACHE=off`` and must write NOTHING under the (scratch)
   artifact root: no disk tier, no sidecars, plan held in RAM only.
5. **Artifact-cache round-trip** — with the cache pointed at a scratch
   root the plan sidecar is written once and a second engine restores it
   (``structure_restored``) bit-identically.
"""

import os
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
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spins", type=int, default=18,
                    help="chain length of the gate config (default 18)")
    args = ap.parse_args()

    scratch = tempfile.mkdtemp(prefix="dmt_stream_check_")
    art_root = os.path.join(scratch, "artifacts")
    os.environ["DMT_ARTIFACT_CACHE"] = "off"
    os.environ["DMT_ARTIFACT_DIR"] = art_root

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    ns = args.spins
    basis = SpinBasis(number_spins=ns, hamming_weight=ns // 2,
                      spin_inversion=1,
                      symmetries=[([*range(1, ns), 0], 0),
                                  ([*reversed(range(ns))], 0)])
    op = heisenberg_from_edges(basis, chain_edges(ns))
    basis.build()
    n = basis.number_states
    assert op.basis.group is not None, "gate config must have |G| > 1"
    print(f"[stream-check] chain_{ns}_symm: N={n}, |G|>1, 2 shards")

    rng = np.random.default_rng(11)
    x = rng.standard_normal(n)
    x /= np.linalg.norm(x)

    eng_f = DistributedEngine(op, n_devices=2, mode="fused")
    eng_s = DistributedEngine(op, n_devices=2, mode="streamed")
    xf, xs = eng_f.to_hashed(x), eng_s.to_hashed(x)

    # -- 1. bit-identity ---------------------------------------------------
    yf = np.asarray(eng_f.matvec(xf))
    ys = np.asarray(eng_s.matvec(xs))
    assert np.array_equal(yf, ys), \
        f"streamed y differs from fused (max |d|={np.abs(yf - ys).max()})"
    assert float(np.vdot(np.asarray(xf), yf)) \
        == float(np.vdot(np.asarray(xs), ys)), "<x,Hx> differs"
    X3 = np.stack([x, -x, 0.5 * x], axis=1)
    Yf = np.asarray(eng_f.matvec(eng_f.to_hashed(X3)))
    Ys = np.asarray(eng_s.matvec(eng_s.to_hashed(X3)))
    assert np.array_equal(Yf, Ys), "k=3 batch differs"
    print("[stream-check] bit-identity: OK (single + k=3 batch + <x,Hx>)")

    # -- 2. counters preserved --------------------------------------------
    obs.health_event_count()          # drains the deferred counter fetches
    counters = obs.snapshot()["counters"]
    for name in ("exchange_overflow", "exchange_invalid"):
        hits = {k: v for k, v in counters.items() if k.startswith(name)}
        assert hits, f"{name} series missing after streamed applies"
        assert all(v == 0 for v in hits.values()), \
            f"nonzero {name} on a healthy run: {hits}"
    print("[stream-check] exchange counters: present at zero")

    # -- 4. pure host-RAM streaming (cache off) ----------------------------
    assert eng_s._plan_chunks is not None and eng_s._plan_disk is None, \
        "plan not resident in host RAM with the artifact layer off"
    assert not os.path.exists(art_root) or not any(os.scandir(art_root)), \
        f"DMT_ARTIFACT_CACHE=off still wrote under {art_root}"
    print("[stream-check] cache-off leg: pure host-RAM, no disk writes")

    # -- 3. less work an apply, by count -----------------------------------
    pev = {e["mode"]: e["phases"] for e in obs.events("apply_phases")
           if e.get("engine") == "distributed"}
    pf, ps = pev["fused"], pev["streamed"]
    assert pf["compute"]["gathers"] > 0 and ps["compute"]["gathers"] == 0, \
        (pf["compute"], ps["compute"])
    assert ps["compute"]["flops"] < pf["compute"]["flops"], \
        (pf["compute"], ps["compute"])
    assert ps["plan_h2d"]["bytes"] == eng_s.plan_bytes \
        and pf["plan_h2d"]["bytes"] == 0, (ps["plan_h2d"], eng_s.plan_bytes)
    assert ps["accumulate"] == pf["accumulate"], \
        (pf["accumulate"], ps["accumulate"])
    print(f"[stream-check] counts an apply: compute flops "
          f"{pf['compute']['flops']} fused -> {ps['compute']['flops']} "
          f"streamed, orbit-scan gathers {pf['compute']['gathers']} -> 0, "
          f"plan_h2d {ps['plan_h2d']['bytes']} B = plan_bytes")

    # -- 5. artifact-cache round-trip --------------------------------------
    os.environ["DMT_ARTIFACT_CACHE"] = "on"
    e1 = DistributedEngine(op, n_devices=2, mode="streamed")
    assert not e1.structure_restored, "fresh cache unexpectedly warm"
    e2 = DistributedEngine(op, n_devices=2, mode="streamed")
    assert e2.structure_restored, "plan sidecar did not restore"
    y2 = np.asarray(e2.matvec(e2.to_hashed(x)))
    assert np.array_equal(y2, ys), "restored plan differs from built plan"
    print("[stream-check] artifact round-trip: saved once, restored "
          "bit-identically")

    print("[stream-check] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
