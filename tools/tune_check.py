#!/usr/bin/env python
"""tune-check — CI gate for the self-tuning runtime (`make tune-check`,
DESIGN.md §30, the `tune=static|live` knob).

Asserts, on 4 virtual CPU devices with an ISOLATED artifact root (the
rig saves deliberately wrong calibrations — they must never leak into
the developer's real cache):

1. **Mis-calibration convergence (deterministic, host-only)** — a
   10x-optimistic flop rate flips the static argmin (the pipeline's
   hide term prices off the compute bound); driving the LiveTuner with
   walls synthesized at the TRUE rates, the first window's
   measured/priced ratio lands outside DRIFT_BAND and proposes a
   re-tune, the ratio converges to within 25% of 1, and the converged
   posterior's re-search lands EXACTLY on the correctly-calibrated
   rig's config (the standing config prices within 25% of that optimum
   under the true rates).  Pure float math — machine-independent.
2. **Live re-key at safe boundaries only (real engine)** — a live-mode
   engine seeded with a wrong tuned artifact under a 50x-optimistic
   calibration drifts at the first window close and re-keys to the
   searched argmin; every `retune` event's apply index sits exactly one
   apply after a window close (never mid-apply), every apply stays
   correct against the dense reference, applies sharing a knob token
   are bit-identical, and the learned posterior persists.
3. **Tuned rates flow to the planner** — `tools/capacity.py`'s
   `--tuning` loader surfaces the posterior (rate_source "posterior")
   and the tuned-config rows, and `price_job` prices at the learned
   rates.
"""

import os
import sys
import tempfile
import time

# platform pins BEFORE any jax import (same discipline as the siblings)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
for var in ("DMT_TUNE", "DMT_TUNE_WINDOW", "DMT_ARTIFACT_DIR",
            "DMT_ARTIFACT_CACHE", "DMT_OBS", "DMT_OBS_DIR",
            "DMT_STREAM_COMPRESS", "DMT_PIPELINE", "DMT_FAULT"):
    os.environ.pop(var, None)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))


def _log(msg):
    print(f"[tune-check] {msg}", flush=True)


def _fail(msg):
    print(f"[tune-check] FAIL: {msg}", flush=True)
    return 1


#: Step 1's geometry: big enough that the compute phase dominates the
#: mis-config's price (complex pairs, k=4 columns, 96 terms), so the
#: 10x flop-rate lie shows up in the measured/priced ratio — AND flips
#: the argmin (cheap believed compute makes the pipeline's
#: min(h2d, comp*w) hide term look worthless).
_STATS = {"shard_size": 131072, "num_terms": 96, "n_my_shards": 1,
          "n_devices": 1, "pair": False, "cplx": True, "columns": 4,
          "group_order": 2, "ram_budget_bytes": 8e9,
          "disk_available": True}


def leg_convergence() -> int:
    """10x-wrong flop rate: drift fires, ratio converges <=25%, the
    converged posterior re-derives the correctly-calibrated config."""
    from distributed_matvec_tpu import tune
    from distributed_matvec_tpu.obs.roofline import (default_calibration,
                                                     phase_bounds_ms)

    # pure host math: artifact layer OFF so this leg's synthetic
    # posteriors never seed the real-engine leg's prior
    os.environ["DMT_ARTIFACT_CACHE"] = "off"
    true_cal = default_calibration("cpu")
    mis = dict(true_cal, flops_per_s=true_cal["flops_per_s"] * 10.0)
    cfg_true = tune.choose_config(_STATS, true_cal, "streamed")
    cfg_mis = tune.choose_config(_STATS, mis, "streamed")
    if cfg_true.same_knobs(cfg_mis):
        return _fail("rig degenerate: the 10x flop lie no longer flips "
                     f"the argmin ({cfg_true.token()})")
    tuner = tune.LiveTuner("streamed", _STATS, mis, cfg_mis, window=4)
    cur = cfg_mis
    tuner.observe(tune.model_counts(_STATS, cur), 0.0)  # compile apply
    ratios, proposals = [], []
    for _ in range(40):
        counts = tune.model_counts(_STATS, cur)
        bounds = phase_bounds_ms(counts, true_cal)
        prop = tuner.observe(counts, sum(bounds.values()),
                             measured={"plan_h2d": bounds["plan_h2d"]})
        if tuner.window_closed:
            ratios.append(tuner.last_ratio)
        if prop is not None:
            proposals.append(prop)
            cur = prop
            tuner.note_rebuild(prop)
            tuner.observe(tune.model_counts(_STATS, cur), 0.0)
    lo, hi = tune.DRIFT_BAND
    if not ratios[0] > hi:
        return _fail(f"first window ratio {ratios[0]:.2f} never left "
                     f"the drift band {tune.DRIFT_BAND}")
    if not proposals:
        return _fail("drift never proposed a re-tune")
    if not abs(ratios[-1] - 1.0) <= 0.25:
        return _fail(f"measured/priced never converged: ratios {ratios}")
    within = next(i for i, r in enumerate(ratios) if abs(r - 1.0) <= 0.25)
    post = tuner.posterior.rates()
    re_search = tune.choose_config(_STATS, post, "streamed")
    if not re_search.same_knobs(cfg_true):
        return _fail("converged posterior re-derives "
                     f"{re_search.token()}, not the correctly-calibrated "
                     f"config {cfg_true.token()}")
    p_cur = tune.price_config(_STATS, cur, true_cal)
    p_opt = tune.price_config(_STATS, cfg_true, true_cal)
    if not p_cur <= 1.25 * p_opt:
        return _fail(f"standing config prices {p_cur:.2f} ms vs optimal "
                     f"{p_opt:.2f} ms under the true rates")
    _log(f"convergence: ratio {ratios[0]:.2f} -> {ratios[-1]:.4f} "
         f"(<=25% after window {within + 1}), re-search "
         f"{re_search.token()} == true argmin, standing config within "
         f"{100.0 * (p_cur / p_opt - 1.0):.2f}% of optimal")
    os.environ["DMT_ARTIFACT_CACHE"] = "on"
    return 0


def leg_live_engine(scratch: str):
    """A real live-mode engine seeded with a WRONG tuned artifact
    re-keys at a window boundary (never mid-apply) to the searched
    argmin, bit-stable between re-keys.  Returns (rc, op) — the op is
    reused by the capacity leg."""
    import numpy as np

    from distributed_matvec_tpu import obs, tune
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.obs.roofline import (default_calibration,
                                                     save_calibration)
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.utils.config import update_config

    import jax

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    cal = default_calibration("cpu")
    # uniformly 50x-optimistic: whatever this CI machine's real speed,
    # measured/priced >> DRIFT_BAND's hi, so the drift MUST fire
    mis = {k: v * 50.0 if isinstance(v, float) else v
           for k, v in cal.items()}
    mis.update(backend="cpu", device_kind=kind)
    save_calibration(mis)

    basis = SpinBasis(12, 6, 1, [([*range(1, 12), 0], 0)])
    basis.build()
    op = heisenberg_from_edges(basis, chain_edges(12))

    # the static rig under the same (wrong) prior: its searched token is
    # what the live drift must re-derive — then poison the artifact
    update_config(tune="static")
    try:
        eng0 = DistributedEngine(op, n_devices=4, mode="streamed")
    finally:
        update_config(tune="off")
    good = eng0._tuned
    stats = eng0._tune_stats()
    fp = eng0._tune_fp
    bad = max((c for c in tune.knob_grid(stats, "streamed")
               if c.plan_tier == "ram" and not c.same_knobs(good)),
              key=lambda c: tune.price_config(stats, c, mis), default=None)
    if bad is None:
        return _fail("grid too small to hold a wrong config"), op
    tune.save_tuned(fp, bad, stats, mis)

    os.environ["DMT_TUNE_WINDOW"] = "3"
    update_config(tune="live")
    try:
        eng = DistributedEngine(op, n_devices=4, mode="streamed")
        if eng._tuned is None or eng._tuned.source != "artifact" \
                or not eng._tuned.same_knobs(bad):
            return _fail("live engine did not restore the seeded "
                         "artifact config"), op
        rng = np.random.default_rng(7)
        x = rng.random(basis.number_states) - 0.5
        ref = op.matvec_host(x)
        xh = eng.to_hashed(x)
        tokens, ys, boundaries = [], [], set()
        for i in range(10):
            y = np.asarray(eng.matvec(xh))
            tokens.append(eng._tuned.token())
            ys.append(y)
            if eng._tuner is not None and eng._tuner.window_closed:
                boundaries.add(i + 1)  # a pending re-key lands at the
                #                        TOP of the next apply
            np.testing.assert_allclose(
                np.asarray(eng.from_hashed(y)), ref,
                atol=1e-10, rtol=1e-10,
                err_msg=f"apply {i} wrong after a re-key")
    finally:
        update_config(tune="off")
        os.environ.pop("DMT_TUNE_WINDOW", None)

    retunes = [e for e in obs.events("retune")
               if e.get("engine") == "distributed"]
    if not retunes:
        return _fail("the 50x lie never triggered a live re-tune"), op
    for e in retunes:
        if int(e["apply"]) not in boundaries:
            return _fail(f"re-key at apply {e['apply']} is NOT one apply "
                         f"after a window close ({sorted(boundaries)}) — "
                         "a mid-apply plan mutation"), op
    if retunes[0]["old_token"] != bad.token():
        return _fail("first re-tune did not replace the seeded bad "
                     "config"), op
    if tokens[-1] != good.token():
        return _fail(f"live loop ended on {tokens[-1]}, not the searched "
                     f"argmin {good.token()}"), op
    # token changes only where a retune event says the plan re-keyed
    changes = {i for i in range(1, len(tokens))
               if tokens[i] != tokens[i - 1]}
    if changes != {int(e["apply"]) for e in retunes}:
        return _fail(f"knob changes at applies {sorted(changes)} vs "
                     f"retune events {retunes}"), op
    for tok in set(tokens):
        grp = [y for y, t in zip(ys, tokens) if t == tok]
        for y in grp[1:]:
            if not np.array_equal(grp[0], y):
                return _fail(f"applies under token {tok} are not "
                             "bit-identical"), op
    if tune.load_posterior("cpu", kind, "streamed") is None:
        return _fail("live loop did not persist its posterior"), op
    _log(f"live engine: {bad.token()} -> {tokens[-1]} at apply "
         f"{retunes[0]['apply']} (ratio {retunes[0]['ratio']}x, window "
         f"boundaries {sorted(boundaries)}), 10/10 applies correct, "
         "bit-stable between re-keys")
    return 0, op


def leg_capacity() -> int:
    """Satellite wiring: the learned posterior and tuned rows reach the
    capacity planner."""
    import capacity

    tuning = capacity.load_tuning()
    if not tuning or "streamed" not in tuning.get("rates", {}):
        return _fail("capacity.load_tuning() missed the live posterior")
    if not tuning.get("configs"):
        return _fail("capacity.load_tuning() missed the tuned artifacts")
    rep = capacity.tuning_report(tuning, tuning["rates"]["streamed"])
    if not rep["rows"]:
        return _fail("tuning_report produced no tuned rows")
    spec = {"n_states": 1 << 20, "num_terms": 24, "mode": "streamed",
            "n_devices": 4}
    verdict = capacity.price_job(spec, tuning["rates"]["streamed"],
                                 tuning=tuning)
    if verdict.get("rate_source") != "posterior":
        return _fail(f"price_job priced at {verdict.get('rate_source')!r},"
                     " not the learned posterior")
    _log(f"capacity: {len(rep['rows'])} tuned row(s), price_job at "
         "posterior rates")
    return 0


def main() -> int:
    t0 = time.time()
    scratch = tempfile.mkdtemp(prefix="dmt_tune_check_")
    # isolated artifact root: the rig's wrong calibrations and poisoned
    # tuned artifacts must never touch the real cache
    os.environ["DMT_ARTIFACT_DIR"] = os.path.join(scratch, "artifacts")
    os.environ["DMT_ARTIFACT_CACHE"] = "on"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    rc = leg_convergence()
    if rc:
        return rc
    rc, _op = leg_live_engine(scratch)
    if rc:
        return rc
    rc = leg_capacity()
    if rc:
        return rc
    _log(f"OK ({time.time() - t0:.0f}s): 10x mis-calibration converges "
         "<=25% onto the true argmin, live re-keys land only at window "
         "boundaries with bit-stable applies, posterior reaches the "
         "capacity planner")
    return 0


if __name__ == "__main__":
    sys.exit(main())
