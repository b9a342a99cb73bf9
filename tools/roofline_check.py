#!/usr/bin/env python
"""roofline-check — CI gate for phase attribution (`make roofline-check`).

Asserts, on the CPU rig (2 virtual devices, chain_<spins>_symm):

1. **HLO byte-identity** — the apply program is byte-identical with phase
   attribution on (`DMT_PHASES=on`, the default) and off, for the local
   ell apply AND the distributed fused apply: phase accounting is
   host-side structural arithmetic, never device work (the health-probe
   contract of DESIGN.md §18 extended to timing).
2. **Model-vs-measured reconciliation** — a streamed run's
   `obs_report roofline` report attributes per-phase wall times that sum
   to the measured apply wall within RECONCILE_TOL (10%), names a binding
   resource from the phase taxonomy, and prints a finite pipelined-apply
   speedup estimate >= 1.  (The sum is an identity of the attribution,
   which divides the measured wall among the phases: it holds on any
   machine at any speed.)
"""

import os
import subprocess
import sys

# platform pins BEFORE any jax import (same discipline as tests/conftest)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
# the gate asserts the DEFAULT enablement and points the sink at its own
# scratch run — inherited telemetry state must not fail it or pollute a
# foreign run dir (same hygiene as the sibling gates)
for var in ("DMT_PHASES", "DMT_OBS", "DMT_OBS_DIR"):
    os.environ.pop(var, None)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

RECONCILE_TOL = 0.10


def main() -> int:
    import argparse
    import json
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spins", type=int, default=16,
                    help="chain length of the gate config (default 16)")
    args = ap.parse_args()

    scratch = tempfile.mkdtemp(prefix="dmt_roofline_check_")
    os.environ["DMT_ARTIFACT_CACHE"] = "off"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import jax.numpy as jnp
    import numpy as np

    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.obs import roofline as R
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    ns = args.spins
    basis = SpinBasis(number_spins=ns, hamming_weight=ns // 2,
                      spin_inversion=1,
                      symmetries=[([*range(1, ns), 0], 0),
                                  ([*reversed(range(ns))], 0)])
    op = heisenberg_from_edges(basis, chain_edges(ns))
    basis.build()
    n = basis.number_states
    print(f"[roofline-check] chain_{ns}_symm: N={n}, 2 shards")
    rng = np.random.default_rng(5)
    x = rng.standard_normal(n)
    x /= np.linalg.norm(x)

    # -- 1. HLO byte-identity, phases on vs off ----------------------------
    def apply_hlo(eng, xarg):
        return jax.jit(eng._apply_fn).lower(
            xarg, eng._operands).compile().as_text()

    el = LocalEngine(op, mode="ell")
    ef = DistributedEngine(op, n_devices=2, mode="fused")
    xj = jnp.asarray(x)
    xh = ef.to_hashed(x)
    assert obs.phases_enabled(), "phases should default on"
    hlo_local_on = apply_hlo(el, xj)
    hlo_dist_on = apply_hlo(ef, xh)
    el.matvec(xj)                     # events flow while enabled
    assert obs.events("apply_phases"), "no apply_phases event emitted"
    os.environ["DMT_PHASES"] = "off"
    try:
        assert not obs.phases_enabled()
        n_ev = len(obs.events("apply_phases"))
        el.matvec(xj)                 # no event, same program
        assert len(obs.events("apply_phases")) == n_ev, \
            "apply_phases emitted with DMT_PHASES=off"
        assert apply_hlo(el, xj) == hlo_local_on, \
            "local apply HLO changed with phases off"
        assert apply_hlo(ef, xh) == hlo_dist_on, \
            "distributed fused apply HLO changed with phases off"
    finally:
        os.environ.pop("DMT_PHASES", None)
    print("[roofline-check] HLO byte-identity (phases on/off): OK")

    # -- 2. model-vs-measured reconciliation on a streamed run -------------
    run_dir = os.path.join(scratch, "run")
    os.environ["DMT_OBS_DIR"] = run_dir
    obs.reset()                        # re-point the sink at the run dir
    # small row chunks → a genuinely multi-chunk plan stream, so the
    # pipelined-apply overlap estimate prices a real chunk pipeline
    es = DistributedEngine(op, n_devices=2, mode="streamed", batch_size=32)
    xs = es.to_hashed(x)
    for _ in range(6):
        yh = es.matvec(xs)
    jax.block_until_ready(yh)
    obs.flush()

    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
         "roofline", run_dir, "--json"],
        capture_output=True, text=True)
    assert r.returncode == 0, f"obs_report roofline failed: {r.stderr}"
    report = json.loads(r.stdout)
    grp = report["groups"].get("distributed/streamed")
    assert grp, f"no streamed group in the roofline report: {report}"
    phase_sum = sum(float(p.get("wall_ms") or 0.0)
                    for p in grp["phases"].values())
    wall = float(grp["wall_ms"])
    err = abs(phase_sum - wall) / max(wall, 1e-9)
    assert err <= RECONCILE_TOL, \
        (f"phase walls sum to {phase_sum:.4f} ms vs measured {wall:.4f} ms "
         f"({err:.1%} > {RECONCILE_TOL:.0%})")
    from distributed_matvec_tpu.obs.phases import PHASES
    assert grp["binding_phase"] in PHASES, grp["binding_phase"]
    assert grp["binding_resource"], "no binding resource named"
    assert int(grp["chunks"]) >= 2, \
        f"expected a multi-chunk stream, got {grp['chunks']} chunk(s)"
    sp = float(grp["pipelined_speedup_estimate"])
    assert sp >= 1.0 and np.isfinite(sp), sp
    print(f"[roofline-check] reconciliation: phases sum {phase_sum:.3f} ms "
          f"vs wall {wall:.3f} ms ({err:.2%} <= {RECONCILE_TOL:.0%}); "
          f"binding: {grp['binding_resource']}; pipelined est {sp:.2f}x")

    # the human-readable rendering must carry the same story
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
         "roofline", run_dir], capture_output=True, text=True)
    assert r.returncode == 0 and "binding resource" in r.stdout \
        and "pipelined-apply estimate" in r.stdout, r.stdout

    print("[roofline-check] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
