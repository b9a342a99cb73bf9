#!/usr/bin/env python
"""health_check — the ``make health-check`` gate for the numerical-health
probes (obs/health.py).

One assertion over a 16-site chain: a probes-on Lanczos solve emits ZERO
``health``/``solver_health`` events and converges — the watchdog
thresholds must stay quiet on a healthy run, or every real alert drowns.
What the probes cost is a time and is not gated here; that they are a
program of their own and add no operation to the apply is
``tests/test_obs.py::test_health_probe_disabled_compiled_out``.

Prints one JSON line and exits 0/1.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    # The gate must own its knobs: health_mode()/obs_enabled() give these
    # env vars precedence over the update_config() toggles below, so an
    # inherited DMT_HEALTH=off would leave the solve unprobed (a vacuous
    # pass) and DMT_OBS=off would disable the layer under test.
    for knob in ("DMT_HEALTH", "DMT_HEALTH_EVERY", "DMT_OBS", "DMT_OBS_DIR"):
        os.environ.pop(knob, None)

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos
    from distributed_matvec_tpu.utils.config import get_config, update_config

    basis = SpinBasis(number_spins=16, hamming_weight=8)
    basis.build()
    op = heisenberg_from_edges(basis, chain_edges(16))
    eng = LocalEngine(op, mode="ell")
    n = basis.number_states

    saved = (get_config().health, get_config().health_every)
    result = {"config": "heisenberg_chain_16", "n_states": n}
    try:
        # cleanliness: probes on, watchdog on — a healthy solve must stay
        # silent (counts BOTH probe events and solver watchdog events)
        update_config(health="on")
        before = obs.health_event_count()
        res = lanczos(eng.matvec, n, k=1, max_iters=80, tol=1e-10, seed=3)
        warnings = obs.health_event_count() - before
        result.update(health_events=warnings,
                      lanczos_converged=bool(res.converged))
        ok_clean = warnings == 0 and res.converged
    finally:
        update_config(health=saved[0], health_every=saved[1])

    result["ok"] = bool(ok_clean)
    print(json.dumps(result))
    if not ok_clean:
        print(f"[health_check] FAIL: {warnings} health event(s) on a "
              "healthy chain-16 solve (expected zero)", file=sys.stderr)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
