#!/usr/bin/env python
"""capacity — offline device-memory capacity planner for the matvec engines.

Answers the questions the engines today answer by trial-and-OOM: how many
bytes does each engine mode spend per basis row, what is the largest basis
one device fits, and how many shards (or which mode) a target basis needs.
Works entirely offline from ONE of three inputs — no device required:

* ``--snapshot RUN`` — an obs run directory or ``.jsonl`` stream: the last
  ``memory_ledger`` event's context fields (mode, n_states, n_padded /
  shard_size, T0, num_terms, table_bytes) calibrate the model with the
  MEASURED bytes of a real engine, and ``memory_analysis`` events supply
  the apply executable's temp bytes.
* ``--structure PATH`` — an engine structure sidecar (``*.structure.h5``,
  explicit path or artifact-cache file): table shapes/dtypes are read
  straight from the checkpoint.
* explicit parameters — ``--n-states``, ``--num-terms``, ``--t0``
  (+ ``--pair`` for (re, im)-f64 sectors): the purely analytic model.

Model (bytes per padded basis row, one device):

    ell      T0 * (4 + cf)         idx i32 + coeff (f64, or 2*f64 pair/c128)
    compact  T0 * 4 + 20           sign-tagged i32 + inv_n f64 + n_parts 3*f32
    fused    0 resident            structure recomputed per apply; scratch is
                                   O(B*T) per chunk, independent of N
    common   ~36 + 8*v*w           diag + basis row + lookup pair, plus v
                                   live vectors of width w (x, y, solver
                                   workspace; v = --vectors, default 3)

When a snapshot/structure is given, the recorded mode's bytes/row is taken
from the measured table bytes instead of the formula (the formula fills in
the other modes), so the report reflects the actual split/tail packing.

Usage::

    python tools/capacity.py --snapshot /tmp/run --hbm-gb 16
    python tools/capacity.py --n-states 63e6 --num-terms 36 --t0 24 \\
        --hbm-gb 16 --target-n 1e9
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Dict, Optional

# per-row overhead shared by every mode: diag f64 + padded alpha u64 +
# norm f64 + lookup pair (2*u32) + directory amortized (~4 B)
COMMON_ROW_BYTES = 36
# utilization headroom: XLA fragmentation + per-apply scratch mean a table
# filling 100% of HBM OOMs long before that
DEFAULT_UTILIZATION = 0.85


def load_snapshot(path: str) -> dict:
    """Calibration facts from an obs run: the LAST ``memory_ledger`` event
    with engine context, plus executable ``memory_analysis`` temp bytes.
    Run loading (rank_*/ layout, legacy files, bare .jsonl) is delegated
    to ``obs_report.load_events`` so the sink layout lives in one place."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import obs_report

    ledger = None
    analyses: Dict[str, dict] = {}
    for ev in obs_report.load_events(path):
        kind = ev.get("kind")
        if kind == "memory_ledger" and ev.get("n_states"):
            ledger = ev
        elif kind == "memory_analysis":
            analyses[str(ev.get("key") or ev.get("program"))] = ev
    if ledger is None:
        raise ValueError(
            f"{path}: no memory_ledger event with engine context — run "
            "with the obs layer on (any engine init emits one)")
    return {"ledger": ledger, "analyses": analyses}


def load_structure(path: str) -> dict:
    """Table geometry straight from a structure sidecar (h5).  Handles
    the LocalEngine layouts (ell: ``level<i>_idx``/``level<i>_coeff``;
    compact: ``idx``) and the DistributedEngine per-shard layout
    (``idx_<d>``/``coeff_<d>``)."""
    import h5py

    with h5py.File(path, "r") as f:
        if "engine_structure" not in f:
            raise ValueError(f"{path}: no /engine_structure group")
        g = f["engine_structure"]
        mode = str(g.attrs.get("mode", "ell"))
        idx_keys = [k for k in g
                    if k == "idx" or k.startswith("idx_")]
        level_keys = [k for k in g
                      if k.startswith("level") and k.endswith("_idx")]
        if not idx_keys and not level_keys:
            raise ValueError(f"{path}: no idx table in the sidecar")
        if level_keys:
            # LocalEngine ell: levels [k, L] (cut into a piece a row block
            # where the engine cut them, or into a near and a far staircase
            # a table range, under the same names); the padded rows are the
            # file's ``n_padded`` (layout v4), else ``pos_of``'s where the
            # rows are ordered by width, else the one level's; T0 is the
            # mean width
            n_pad = int(g.attrs["n_padded"]) if "n_padded" in g.attrs \
                else int(g["pos_of"].shape[0]) if "pos_of" in g \
                else max(int(g[k].shape[-1]) for k in level_keys)
            slots = sum(int(g[k].size) for k in level_keys)
            T0 = -(-slots // max(n_pad, 1))
        else:
            T0 = int(g.attrs.get("T0", g[idx_keys[0]].shape[0]))
            # local: one [T0, N_pad] table; distributed: [T0, M] per shard
            n_pad = sum(int(g[k].shape[-1]) for k in idx_keys)
        table_bytes = sum(int(g[k].size) * g[k].dtype.itemsize for k in g)
        coeff_keys = [k for k in g
                      if k == "coeff" or k.startswith("coeff_")
                      or (k.startswith("level") and k.endswith("_coeff"))]
        pair = cplx = False
        if coeff_keys:
            c = g[coeff_keys[0]]
            pair = bool(c.ndim >= 3 and c.shape[-1] == 2)
            cplx = c.dtype.kind == "c"
        return {"mode": mode, "T0": T0, "n_padded": n_pad,
                "n_states": n_pad, "table_bytes": table_bytes,
                "pair": pair or cplx}


def mode_bytes_per_row(T0: int, pair: bool) -> Dict[str, float]:
    """The analytic per-row structure cost of each mode (DEVICE bytes;
    streamed/hybrid keep no resident structure on device — their plans
    live in host RAM, see :func:`stream_plan_bytes_per_row`)."""
    cf = 16 if pair else 8
    return {"ell": T0 * (4 + cf),
            "compact": T0 * 4 + 20,
            "streamed": 0.0,
            "hybrid": 0.0,
            "fused": 0.0}


#: stream_compress settings the planner models (ops/plan_codec.py tiers).
STREAM_COMPRESS_SETTINGS = ("off", "lossless", "f32", "bf16")

#: Live-entry share of a compacted plan (the codec stores only entries
#: whose coefficient is nonzero): measured ~52% live on Heisenberg
#: chains.  A documented model constant — measured calibration wins.
LIVE_FRACTION = 0.55

#: Row-chunk size assumed when pricing the pipelined streamed tier (the
#: engine's ``matvec_batch_size`` default): the pipelined estimate's
#: ``1 − 1/nchunks`` factor needs a chunk count, and the planner has no
#: engine in hand.
PIPELINE_CHUNK_ROWS = 1 << 16

#: Modeled SPREAD of per-term live fractions for the offline hybrid
#: split (DESIGN.md §28): real operators' terms fire at different rates
#: (the measured 48% dead share on chain_24_symm is an AVERAGE over
#: terms), so the planner spreads the per-term liveness linearly over
#: ``LIVE_FRACTION · [1−spread, 1+spread]`` — enough heterogeneity for
#: the priced split to land mid-way when the rates put the break-even
#: inside the spread.  A documented model constant, same standing as
#: ``LIVE_FRACTION`` — an engine's measured census (the ``auto`` split
#: at build time) always wins.
HYBRID_LIVE_SPREAD = 0.5

#: Share of a compacted-tier plan row the SHARED receive layout
#: (bitpacked ridx/rok) occupies — it streams per chunk regardless of
#: which terms the split stores, so a partial-term plan's bytes floor at
#: this fraction of the full row (measured 0.39–0.40 on the lossless
#: tier: 115056/288864 B on the tfxy_12 all-recompute gate engine,
#: 2827968/7288512 B on the tfxy_16 mixed split — `make hybrid-check`).
HYBRID_SHARED_ROW_FRACTION = 0.4


def stream_plan_bytes_per_row(num_terms: int, pair: bool,
                              compress: str = "off") -> float:
    """HOST bytes per basis row of a streamed engine's resolved plan:
    dest index + coefficient per (row, term); the per-chunk receive
    layout (ridx + rok per exchange slot) adds a few percent and is
    folded into a flat overhead rather than modeled exactly.

    Compressed settings (``ops/plan_codec.py``): only LIVE entries are
    stored (``LIVE_FRACTION`` models the Heisenberg-class dead share —
    measured 48% dead on chain_24_symm; operators where every term fires
    on every row should read the measured calibration instead),
    destination+row indices bitpack to ~4 B/live entry, and the
    receive-layout overhead drops 10% → 8% (capacity trimmed, ridx
    packed, rok 1 bit).  Coefficients: ``lossless`` assumes u16
    dictionary codes (symm-sector coefficients repeat; a dict overflow
    falls back to raw f64 and the measured calibration then wins);
    ``f32``/``bf16`` are modeled in their raw-quantized form — the
    tiers exist for operators whose coefficients do NOT repeat enough
    to dictionary-code."""
    cf = 16 if pair else 8
    if compress in (None, "", "off"):
        return num_terms * (4 + cf) * 1.10
    ncomp = 2 if pair else 1
    coeff_b = {"lossless": 2.0, "f32": 4.0 * ncomp,
               "bf16": 2.0 * ncomp}[compress]
    return num_terms * (4.0 + coeff_b) * LIVE_FRACTION * 1.08


def hybrid_split_model(n_states: int, num_terms: int, pair: bool,
                       n_devices: int, group_order: int,
                       rates: Optional[dict],
                       eff_tier: str) -> Optional[dict]:
    """Offline model of the hybrid mode's per-term split (DESIGN.md §28),
    pricing through the SAME :func:`~distributed_matvec_tpu.obs.roofline.
    price_term_split` the engine's ``auto`` policy uses — so the planner,
    the engine, and ``price_job`` agree on the economics.

    Per-term live fractions are modeled as a linear
    ``LIVE_FRACTION·[1±HYBRID_LIVE_SPREAD]`` spread (an engine's measured
    census wins at build time); ``group_order`` is |G| (``--group-order``
    — 1 for unprojected sectors, where recompute is cheapest).  None when
    no usable rate calibration is available."""
    if not (rates and all(rates.get(k) for k in
                          ("flops_per_s", "gather_rows_per_s",
                           "h2d_bytes_per_s"))):
        return None
    try:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from distributed_matvec_tpu.obs import roofline as _roofline
    except ImportError:
        return None
    import numpy as np

    T = max(int(num_terms), 1)
    rows_share = n_states / max(n_devices, 1)
    spread = np.linspace(1.0 - HYBRID_LIVE_SPREAD,
                         1.0 + HYBRID_LIVE_SPREAD, T)
    live_frac = np.clip(LIVE_FRACTION * spread, 0.02, 1.0)
    live = live_frac * rows_share
    ncomp = 2 if pair else 1
    coeff_b = {"lossless": 2.0, "f32": 4.0 * ncomp,
               "bf16": 2.0 * ncomp}[eff_tier]
    res = _roofline.price_term_split(live, rows_share,
                                     max(int(group_order), 1), rates,
                                     4.0 + coeff_b, cplx=pair)
    mask = np.asarray(res["stream_mask"], bool)
    total_live = float(live.sum())
    return {"stream_mask": mask,
            "stream_terms": int(mask.sum()), "num_terms": T,
            "stream_term_fraction": float(mask.mean()),
            "stream_live_fraction":
            (float(live[mask].sum()) / total_live if total_live else 1.0),
            "stream_ms": res["stream_ms"],
            "recompute_ms": res["recompute_ms"],
            "live_frac": live_frac, "eff_tier": eff_tier,
            "group_order": max(int(group_order), 1)}


#: The rate fields an overlay must carry to replace a calibration in the
#: pricing paths (mirrors ``obs/roofline.RATE_FIELDS`` without the import).
TUNE_RATE_FIELDS = ("gather_rows_per_s", "h2d_bytes_per_s",
                    "exchange_bytes_per_s", "flops_per_s")


def load_tuning(backend: Optional[str] = None,
                device_kind: Optional[str] = None) -> Optional[dict]:
    """The tune/ subsystem's persisted state (DESIGN.md §30): live-rate
    posteriors per mode plus the most recent tuned-config artifact per
    mode.  What ``--tuning`` (and the serve scheduler) folds into
    admission pricing — the posterior's LEARNED rates replace the static
    calibration, and each tuned config becomes a candidate row the
    recommendation can prefer over the catalog modes.  None when the
    tune package is unavailable or nothing has been persisted."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from distributed_matvec_tpu import tune as _tune
    except ImportError:
        return None
    out = {"rates": {}, "configs": []}
    for mode in ("streamed", "hybrid"):
        try:
            post = _tune.load_posterior(backend, device_kind, mode)
        except Exception:
            post = None
        if post and all(post.get(k) for k in TUNE_RATE_FIELDS):
            out["rates"][mode] = post
        try:
            docs = _tune.find_tuned(mode, backend)
        except Exception:
            docs = []
        if docs:
            out["configs"].append(docs[0])
    return out if (out["rates"] or out["configs"]) else None


def tuning_report(tuning: dict, rates: Optional[dict]) -> dict:
    """The report's ``tuning`` section: each persisted tuned config
    re-priced under the effective rates (posterior when one exists —
    falling back to the artifact's save-time price), plus the posterior
    provenance per mode."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from distributed_matvec_tpu import tune as _tune

    rows = []
    for doc in tuning.get("configs", []):
        try:
            cfg = _tune.TunedConfig.from_dict(doc["config"])
            ms = cfg.priced_ms
            if rates and all(rates.get(k) for k in TUNE_RATE_FIELDS):
                try:
                    # artifact stats are canonicalized (floats as .6g
                    # strings) — decode before re-pricing
                    stats = {}
                    for k, v in (doc.get("stats") or {}).items():
                        if isinstance(v, str):
                            f = float(v)
                            v = int(f) if f.is_integer() else f
                        stats[k] = v
                    ms = _tune.price_config(stats, cfg, rates)
                except Exception:
                    pass
            rows.append({
                "mode": str(doc.get("mode")), "token": cfg.token(),
                "est_apply_ms": (round(float(ms), 3)
                                 if ms is not None else None),
                "rate_source": str((rates or {}).get(
                    "source", doc.get("rate_source", ""))),
                "fingerprint": str(doc.get("fingerprint", ""))[:12]})
        except Exception:
            continue
    return {"rows": rows,
            "posteriors": {m: {"source": r.get("source"),
                               "n_updates": int(r.get("n_updates") or 0)}
                           for m, r in tuning.get("rates", {}).items()}}


def load_rate_calibration(path: Optional[str] = None) -> Optional[dict]:
    """The measured-rates calibration sidecar ``tools/gather_bound.py``
    persists (``obs/roofline.py``) — explicit path, else the
    content-addressed default; None when neither exists.  Shared with the
    roofline report so both planners price applies at the same rates.
    An explicit path that does not load raises (never a silent drop of
    the est_apply_ms column the user asked for)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from distributed_matvec_tpu.obs import roofline
    except ImportError:
        return None
    cal = roofline.load_calibration(path)
    if path and cal is None:
        raise FileNotFoundError(
            f"calibration file {path} is missing or carries no rate "
            "fields (expected a tools/gather_bound.py JSON)")
    return cal


def plan(n_states: int, num_terms: int, T0: int, pair: bool,
         hbm_gb: float, n_devices: int, vectors: int, vec_width: int,
         measured: Optional[dict] = None,
         utilization: float = DEFAULT_UTILIZATION,
         host_ram_gb: float = 64.0,
         rates: Optional[dict] = None,
         stream_compress: str = "off",
         group_order: int = 1) -> dict:
    """The capacity report: bytes/row, max basis per device and per mesh
    for each mode, plus (optionally) measured calibration.  The streamed
    mode is additionally bounded by HOST RAM (``host_ram_gb``, per rank —
    one rank per device assumed): its resolved plan streams from there,
    so the binding constraint is min(device rows, host plan rows) — and
    the plan is the ENCODED stream at the chosen ``stream_compress``
    setting (every setting's bytes/row rides along in
    ``host_plan_bytes_per_row_by_compress``).  With a ``rates``
    calibration (gather_bound sidecar) each mode also gets an
    ``est_apply_ms`` gather/stream-bound apply-time estimate; the
    streamed estimate prices the *encoded* H2D bytes, so compression
    shows up directly in the est ms/apply column."""
    T0 = int(T0) if T0 else int(num_terms)
    if stream_compress not in STREAM_COMPRESS_SETTINGS:
        raise ValueError(f"unknown stream_compress {stream_compress!r}")
    per_mode = mode_bytes_per_row(T0, pair)
    plan_row_by = {s: stream_plan_bytes_per_row(int(num_terms), pair, s)
                   for s in STREAM_COMPRESS_SETTINGS}
    vec_bytes = 8 * vectors * max(vec_width, 1) * (2 if pair else 1)
    common = COMMON_ROW_BYTES + vec_bytes
    budget = hbm_gb * 1e9 * utilization
    host_budget = host_ram_gb * 1e9 * utilization
    out = {"inputs": {"n_states": int(n_states), "num_terms": int(num_terms),
                      "T0": T0, "pair": bool(pair), "hbm_gb": hbm_gb,
                      "host_ram_gb": host_ram_gb,
                      "n_devices": int(n_devices), "vectors": vectors,
                      "vec_width": vec_width, "utilization": utilization,
                      "stream_compress": stream_compress},
           "modes": {}}
    if measured:
        out["calibration"] = measured
        mmode = measured.get("mode")
        n_pad = measured.get("n_padded") or measured.get("n_states")
        if mmode in per_mode and measured.get("table_bytes") and n_pad:
            per_mode[mmode] = measured["table_bytes"] / float(n_pad)
            out["calibration"] = dict(
                measured, bytes_per_row_measured=round(per_mode[mmode], 2))
        if mmode == "streamed" and measured.get("plan_bytes") and n_pad:
            # the ledger's plan_bytes are the ENCODED bytes at the
            # recorded stream_compress setting; anchor that setting on
            # the measurement (and "off" on plan_bytes_raw when present),
            # then scale the un-measured settings by the model's ratios
            mcomp = str(measured.get("stream_compress") or "off")
            if mcomp not in plan_row_by:
                mcomp = "off"
            model = dict(plan_row_by)      # pre-anchor model ratios
            anchor_row = measured["plan_bytes"] / float(n_pad)
            raw_row = (measured["plan_bytes_raw"] / float(n_pad)
                       if measured.get("plan_bytes_raw") else None)
            for s in STREAM_COMPRESS_SETTINGS:
                if s == mcomp:
                    plan_row_by[s] = anchor_row
                elif s == "off" and raw_row is not None:
                    plan_row_by[s] = raw_row
                else:
                    plan_row_by[s] = anchor_row * model[s] / model[mcomp]
            out["calibration"] = dict(
                out["calibration"],
                plan_bytes_per_row_measured=round(anchor_row, 2),
                plan_bytes_per_row_compress=mcomp)
    plan_row = plan_row_by[stream_compress]
    # hybrid encodes at the compacted tier (compress "off" maps to
    # lossless — a term subset cannot ride the raw layout), and its
    # split is modeled through the shared roofline pricer
    hyb_tier = "lossless" if stream_compress in (None, "", "off") \
        else stream_compress
    hyb = hybrid_split_model(int(n_states), int(num_terms), bool(pair),
                             int(n_devices), int(group_order), rates,
                             hyb_tier)
    out["inputs"]["group_order"] = int(group_order)
    if rates:
        out["rates"] = {k: rates.get(k) for k in
                        ("gather_rows_per_s", "h2d_bytes_per_s",
                         "backend", "device_kind", "source")}
    rows_share = n_states / max(n_devices, 1)
    for mode, struct_bytes in per_mode.items():
        row = struct_bytes + common
        rows_dev = int(budget // row)
        entry = {
            "structure_bytes_per_row": round(struct_bytes, 2),
            "bytes_per_row": round(row, 2),
        }
        if mode == "streamed":
            entry["host_plan_bytes_per_row"] = round(plan_row, 2)
            entry["stream_compress"] = stream_compress
            entry["host_plan_bytes_per_row_by_compress"] = {
                s: round(r, 2) for s, r in plan_row_by.items()}
            rows_dev = min(rows_dev, int(host_budget // plan_row))
        elif mode == "hybrid":
            # the hybrid plan stores the streamed term subset only: host
            # bytes shrink by the recomputed terms' live share, floored
            # at the shared ridx/rok receive layout's share of the row
            # (it streams per chunk regardless of the split)
            frac = hyb["stream_live_fraction"] if hyb else 1.0
            row_h = plan_row_by[hyb_tier] * (
                HYBRID_SHARED_ROW_FRACTION
                + (1.0 - HYBRID_SHARED_ROW_FRACTION) * frac)
            entry["host_plan_bytes_per_row"] = round(row_h, 2)
            entry["stream_compress"] = hyb_tier
            if hyb:
                entry["hybrid_stream_terms"] = hyb["stream_terms"]
                entry["hybrid_stream_term_fraction"] = round(
                    hyb["stream_term_fraction"], 4)
            rows_dev = min(rows_dev, int(host_budget // max(row_h, 1.0)))
            if rates and rates.get("h2d_bytes_per_s"):
                # priced split: the streamed share at the h2d floor plus
                # the recomputed terms' orbit-scan flops.  NB the pure
                # streamed row is priced at the CONFIGURED tier while
                # hybrid always rides the compacted tier, so hybrid's
                # est undercuts both pure tiers when the recompute
                # credit (and, off-tier, the forced compaction) is
                # decisive — near the per-term break-even a mixed split
                # prices close to pure streamed, which is the honest
                # reading of break-even economics
                h2d_ms = rows_share * row_h \
                    / float(rates["h2d_bytes_per_s"]) * 1e3
                rec_ms = float(hyb["recompute_ms"][
                    ~hyb["stream_mask"]].sum()) if hyb else 0.0
                entry["est_apply_ms"] = round(h2d_ms + rec_ms, 3)
        if rates and rates.get("gather_rows_per_s"):
            # gather-roofline apply-time estimate per device shard at the
            # calibrated rates: ell/compact gather T0 entries/row; fused
            # scans T per row (the orbit-scan constant is in the flops
            # term the roofline model carries — this is the gather floor);
            # streamed is bounded by its plan stream (h2d bytes)
            g = float(rates["gather_rows_per_s"])
            if mode in ("ell", "compact", "fused"):
                per = T0 if mode in ("ell", "compact") else int(num_terms)
                entry["est_apply_ms"] = round(
                    rows_share * per / g * 1e3, 3)
            elif mode == "streamed" and rates.get("h2d_bytes_per_s"):
                h2d_ms = rows_share * plan_row \
                    / float(rates["h2d_bytes_per_s"]) * 1e3
                entry["est_apply_ms"] = round(h2d_ms, 3)
                # pipelined streamed tier (DESIGN.md §25): price the
                # whole apply wall (plan stream + chunk compute +
                # amplitude exchange at the calibrated rates), then take
                # back the roofline's overlap term
                # min(compute, exchange+stream)·(1 − 1/nchunks) — what a
                # pipeline_depth >= 2 apply is priced to cost, so the
                # recommendation can prefer it
                if rates.get("flops_per_s") \
                        and rates.get("exchange_bytes_per_s"):
                    live = LIVE_FRACTION \
                        if stream_compress not in (None, "", "off") else 1.0
                    ent_rows = rows_share * num_terms * live
                    compute_ms = ent_rows * 2 \
                        / float(rates["flops_per_s"]) * 1e3
                    exch_ms = (ent_rows * 8
                               / float(rates["exchange_bytes_per_s"]) * 1e3
                               if n_devices > 1 else 0.0)
                    nch = max(int(math.ceil(
                        rows_share / PIPELINE_CHUNK_ROWS)), 1)
                    wall = h2d_ms + compute_ms + exch_ms
                    overlap = (min(compute_ms, exch_ms + h2d_ms)
                               * (1.0 - 1.0 / nch)) if nch > 1 else 0.0
                    entry["est_apply_ms_pipelined"] = round(
                        max(wall - overlap, 0.0), 3)
                    entry["pipeline_nchunks_assumed"] = nch
        entry.update({
            "max_rows_per_device": rows_dev,
            "max_basis_size": rows_dev * n_devices,
            "fits_n_states": bool(n_states <= rows_dev * n_devices),
            "devices_needed_for_n_states":
                max(1, math.ceil(n_states / rows_dev)) if rows_dev else None,
        })
        out["modes"][mode] = entry
    return out


#: Solve-length model for :func:`price_job`: Lanczos columns to
#: convergence per requested eigenpair (Heisenberg-class spectra reach
#: 1e-10 residuals well inside this on the chain configs).  A documented
#: model constant, same standing as ``LIVE_FRACTION``.
EST_COLUMNS_PER_EIGENPAIR = 48

#: Dynamics solve-length models (DESIGN.md §29), in the same matvec-
#: COLUMN units the eigensolver model uses, so every solver kind prices
#: through the one calibrated `est ms/apply` rate:
#:  * kpm — the doubling recurrence takes ~n_moments/2 block applies of
#:    n_vectors columns each, plus the spectral-bounds Lanczos pass;
#:  * evolve — ~EVOLVE_STEPS_PER_UNIT_TIME accepted steps per unit
#:    time at the default tolerance, each step krylov_dim applies of a
#:    2-column (Re, Im) block.
#: Documented model constants with the same standing as
#: EST_COLUMNS_PER_EIGENPAIR.
KPM_BOUNDS_COLUMNS = 64
EVOLVE_STEPS_PER_UNIT_TIME = 8


def price_job(spec, calibration: Optional[dict] = None,
              hbm_gb: float = 16.0, host_ram_gb: float = 64.0,
              utilization: float = DEFAULT_UTILIZATION,
              vectors: int = 3, tuning: Optional[dict] = None) -> dict:
    """Admission pricing for ONE job spec — the importable API the solve
    service's scheduler (``distributed_matvec_tpu/serve/scheduler.py``)
    and its tests call instead of shelling out to the CLI.

    ``spec`` is a mapping with ``n_states``/``num_terms``/``mode``/
    ``n_devices`` (+ optional ``pair``/``k``/``max_iters``/``t0``) — what
    ``JobSpec.pricing()`` produces.  ``calibration`` is a rates dict from
    :func:`load_rate_calibration` (or any mapping with
    ``gather_rows_per_s`` etc.); None prices memory fits only.
    ``tuning`` is a :func:`load_tuning` record: when it carries a live
    posterior for the spec's mode, THOSE learned rates price the job —
    admission tracks what the hardware actually did, not the catalog.

    Returns ``{est_apply_ms, est_solve_s, fits, est_iters, reason}``:
    ``fits`` is the memory verdict for the spec's mode on its mesh (the
    streamed mode's host-plan budget included), ``est_apply_ms`` the
    calibrated roofline apply estimate (None without rates), and
    ``est_solve_s`` that estimate times the modeled iteration count
    (``EST_COLUMNS_PER_EIGENPAIR``·k, capped by the spec's own
    ``max_iters``).  A spec whose dimension is unknown before the basis
    builds (yaml submissions) is passed through un-priced with
    ``fits=True`` — admission stays optimistic rather than rejecting
    blind."""
    n_states = spec.get("n_states")
    if not n_states:
        return {"est_apply_ms": None, "est_solve_s": None, "fits": True,
                "est_iters": None, "priced": False,
                "reason": "unpriced (dimension unknown before basis build)"}
    mode = str(spec.get("mode") or "ell")
    rate_source = (calibration or {}).get("source")
    if tuning and tuning.get("rates"):
        post = tuning["rates"].get(mode) \
            or next(iter(tuning["rates"].values()), None)
        if post and all(post.get(k) for k in TUNE_RATE_FIELDS):
            calibration = post
            rate_source = post.get("source", "posterior")
    num_terms = int(spec.get("num_terms") or 1)
    k = max(int(spec.get("k") or 1), 1)
    report = plan(int(n_states), num_terms,
                  int(spec.get("t0") or num_terms),
                  bool(spec.get("pair")), float(hbm_gb),
                  max(int(spec.get("n_devices") or 1), 1),
                  vectors, max(k, 2), utilization=utilization,
                  host_ram_gb=float(host_ram_gb), rates=calibration,
                  group_order=max(int(spec.get("group_order") or 1), 1))
    entry = report["modes"].get(mode)
    if entry is None:
        return {"est_apply_ms": None, "est_solve_s": None, "fits": False,
                "est_iters": None, "priced": False,
                "reason": f"unknown engine mode {mode!r}"}
    fits = bool(entry["fits_n_states"])
    est_apply_ms = entry.get("est_apply_ms")
    solver = str(spec.get("solver") or "eigs")
    if solver == "kpm":
        # moment recurrence: ceil(n_moments/2) block applies of
        # n_vectors columns, plus the bounds pass
        est_iters = (int(spec.get("n_moments") or 256) + 1) // 2 \
            * max(int(spec.get("n_vectors") or 4), 1) + KPM_BOUNDS_COLUMNS
    elif solver == "evolve":
        # trajectory: steps/unit-time x krylov applies x the 2-column
        # (Re, Im) block a complex state rides on a real engine
        import math as _math
        steps = max(int(_math.ceil(
            EVOLVE_STEPS_PER_UNIT_TIME * float(spec.get("t_final") or 1.0))),
            1)
        est_iters = steps * max(int(spec.get("krylov_dim") or 24), 2) * 2
    else:
        est_iters = min(EST_COLUMNS_PER_EIGENPAIR * k,
                        int(spec.get("max_iters") or 10 ** 9))
    # 6 decimals: a sub-millisecond solve must price > 0, or a long
    # queue of tiny jobs would never grow the admission backlog
    est_solve_s = (round(est_apply_ms * est_iters / 1e3, 6)
                   if est_apply_ms is not None else None)
    reason = "" if fits else (
        f"{mode} needs {entry['devices_needed_for_n_states']} device(s) "
        f"for {int(n_states):,} rows, mesh has "
        f"{report['inputs']['n_devices']}")
    return {"est_apply_ms": est_apply_ms, "est_solve_s": est_solve_s,
            "fits": fits, "est_iters": est_iters, "priced": True,
            "reason": reason, "rate_source": rate_source,
            "bytes_per_row": entry["bytes_per_row"],
            "max_rows_per_device": entry["max_rows_per_device"]}


def recommend(report: dict, target_n: Optional[int]) -> dict:
    """Mode/shard recommendation for ``target_n`` (or the input basis):
    the cheapest-per-apply mode (ell > compact > streamed > fused
    preference order matches measured apply speed — streamed beats fused
    whenever its plan fits the RAM/disk budget, because steady applies
    skip the whole orbit scan) that fits within the given mesh, else the
    minimal shard count per mode.  With a rate calibration in hand the
    fitting modes are instead ranked by their ``est_apply_ms`` floors
    (homogeneous single-resource bounds — ranking a full-wall estimate
    against another mode's floor would bias the choice); when the winner
    is ``streamed`` and the pipelined tier is priced, the recommendation
    says to run it with ``pipeline_depth=auto`` (the pipelined wall beats
    the sequential streamed wall by construction whenever there is more
    than one chunk)."""
    n = int(target_n or report["inputs"]["n_states"])
    D = report["inputs"]["n_devices"]
    rec = {"target_n": n}
    options = []
    for mode in ("ell", "compact", "streamed", "hybrid", "fused"):
        m = report["modes"][mode]
        need = max(1, math.ceil(n / m["max_rows_per_device"])) \
            if m["max_rows_per_device"] else None
        options.append((mode, need))
        rec[f"devices_needed_{mode}"] = need
    fitting = [(mode, need) for mode, need in options
               if need is not None and need <= D]
    if fitting:
        # unpriced preference order: hybrid only wins through the est
        # ranking below — without rates there is no split to price, so
        # the documented ell > compact > streamed > fused order stands
        unpriced = [o for o in fitting if o[0] != "hybrid"] or fitting
        rec["recommended_mode"], rec["recommended_devices"] = unpriced[0]
        pipelined_won = False
        ests = {mode: report["modes"][mode].get("est_apply_ms")
                for mode, _need in fitting}
        if all(e is not None for e in ests.values()):
            best = min(fitting, key=lambda o: ests[o[0]])
            rec["recommended_mode"], rec["recommended_devices"] = best
            rec["est_apply_ms"] = ests[best[0]]
            pipe_est = report["modes"]["streamed"].get(
                "est_apply_ms_pipelined")
            if best[0] == "streamed" and pipe_est is not None:
                pipelined_won = True
                rec["est_apply_ms_pipelined"] = pipe_est
        hybrid_note = ""
        if rec["recommended_mode"] == "hybrid":
            hm = report["modes"]["hybrid"]
            rec["recommended_hybrid_split"] = "auto"
            if "hybrid_stream_term_fraction" in hm:
                hybrid_note = (
                    f" (priced split: ~{hm['hybrid_stream_terms']}"
                    f"/{report['inputs']['num_terms']} terms streamed — "
                    "run with hybrid_split=auto / DMT_HYBRID=auto)")
        rec["note"] = (f"{rec['recommended_mode']} fits {n:,} rows on "
                       f"{rec['recommended_devices']} of {D} device(s)"
                       + (" (priced pipelined: run with "
                          "pipeline_depth=auto / DMT_PIPELINE=auto)"
                          if pipelined_won else "") + hybrid_note)
        if pipelined_won:
            rec["recommended_pipeline"] = "auto"
        # a tuned row BEATS the catalog rows (DESIGN.md §30): the
        # autotuner priced the full knob cross-product for a real
        # engine's geometry — when its config's mode fits this mesh and
        # its price is no worse than the catalog pick, recommend running
        # it (tune=static restores the exact artifact, search skipped)
        tuned = (report.get("tuning") or {}).get("rows") or []
        best_row = None
        for row in tuned:
            need = rec.get(f"devices_needed_{row['mode']}")
            est = row.get("est_apply_ms")
            if need is None or need > D or est is None:
                continue
            if best_row is None or est < best_row["est_apply_ms"]:
                best_row = row
        if best_row is not None and (
                rec.get("est_apply_ms") is None
                or best_row["est_apply_ms"] <= rec["est_apply_ms"]):
            rec["recommended_mode"] = best_row["mode"]
            rec["recommended_devices"] = rec[
                f"devices_needed_{best_row['mode']}"]
            rec["est_apply_ms"] = best_row["est_apply_ms"]
            rec["tuned_config"] = best_row["token"]
            rec["note"] = (
                f"tuned {best_row['mode']} config {best_row['token']} "
                f"prices {best_row['est_apply_ms']:,.2f} ms/apply — run "
                "with tune=static (DMT_TUNE=static); " + rec["note"])
    else:
        # minimal-shard fallback: ties break AWAY from hybrid (fused
        # matches its device bytes without the host-plan dependency)
        mode, need = min((o for o in options if o[1] is not None),
                         key=lambda o: (o[1], o[0] == "hybrid"),
                         default=(None, None))
        rec["recommended_mode"], rec["recommended_devices"] = mode, need
        rec["note"] = (f"no mode fits {n:,} rows on {D} device(s); "
                       f"{mode} needs >= {need} shards")
    return rec


def print_report(report: dict, rec: dict) -> None:
    ins = report["inputs"]
    print(f"capacity plan: N={ins['n_states']:,} T={ins['num_terms']} "
          f"T0={ins['T0']} pair={ins['pair']} "
          f"HBM/device={ins['hbm_gb']} GB x{ins['utilization']:.0%} "
          f"devices={ins['n_devices']}")
    cal = report.get("calibration")
    if cal:
        print(f"  calibrated from a measured {cal.get('mode')} engine: "
              f"{cal.get('table_bytes', 0) / 1e9:.3f} GB tables"
              + (f" = {cal['bytes_per_row_measured']} B/row"
                 if "bytes_per_row_measured" in cal else ""))
    rates = report.get("rates")
    if rates:
        print(f"  rate calibration ({rates.get('source')}, "
              f"{rates.get('backend')}): gather "
              f"{(rates.get('gather_rows_per_s') or 0) / 1e6:.0f} M rows/s, "
              f"h2d {(rates.get('h2d_bytes_per_s') or 0) / 1e9:.1f} GB/s")
    est_col = any("est_apply_ms" in report["modes"][m]
                  for m in report["modes"])
    print(f"  {'mode':<9} {'struct B/row':>13} {'total B/row':>12} "
          f"{'max rows/device':>16} {'max basis (mesh)':>17}"
          + (f" {'est ms/apply':>13}" if est_col else "") + "  fits N?")
    for mode in ("ell", "compact", "streamed", "hybrid", "fused"):
        m = report["modes"][mode]
        note = (f"  (+{m['host_plan_bytes_per_row']:.0f} B/row host plan, "
                f"stream_compress={m['stream_compress']})"
                if "host_plan_bytes_per_row" in m else "")
        est = (f" {m['est_apply_ms']:>13,.1f}" if "est_apply_ms" in m
               else (" " * 14 if est_col else ""))
        print(f"  {mode:<9} {m['structure_bytes_per_row']:>13.1f} "
              f"{m['bytes_per_row']:>12.1f} "
              f"{m['max_rows_per_device']:>16,} "
              f"{m['max_basis_size']:>17,} {est} "
              f"{'yes' if m['fits_n_states'] else 'no'}{note}")
        if "host_plan_bytes_per_row_by_compress" in m:
            by = m["host_plan_bytes_per_row_by_compress"]
            print("            host plan B/row by stream_compress: "
                  + "  ".join(f"{s}={by[s]:.0f}" for s in by))
        if "est_apply_ms_pipelined" in m:
            print(f"            pipelined (depth>=2, "
                  f"~{m['pipeline_nchunks_assumed']} chunks): est "
                  f"{m['est_apply_ms_pipelined']:,.1f} ms/apply "
                  f"(wall minus min(compute, exchange+stream)"
                  f"·(1-1/n))")
        if "hybrid_stream_term_fraction" in m:
            print(f"            priced split (|G|="
                  f"{ins.get('group_order', 1)}): "
                  f"{m['hybrid_stream_terms']}/{ins['num_terms']} terms "
                  f"streamed ({m['hybrid_stream_term_fraction']:.0%}), "
                  "rest recomputed on device")
    tun = report.get("tuning")
    if tun and tun.get("rows"):
        print("  tuned configs (tune/ artifacts, --tuning):")
        for row in tun["rows"]:
            est = (f"est {row['est_apply_ms']:,.2f} ms/apply"
                   if row.get("est_apply_ms") is not None else "unpriced")
            print(f"    {row['mode']:<9} {row['token']}  {est}  "
                  f"[{row['rate_source'] or 'saved'} rates, "
                  f"fp {row['fingerprint']}]")
    print(f"  recommendation: {rec['note']}")


def print_hybrid_terms(report: dict, hyb: Optional[dict]) -> None:
    """The ``--hybrid`` per-term cost table: each modeled term's stream
    vs recompute price at the calibrated rates, and which side the
    priced split puts it on (DESIGN.md §28)."""
    if not hyb:
        print("  hybrid term table: no usable rate calibration "
              "(pass --calibration or run tools/gather_bound.py)")
        return
    print(f"  hybrid per-term costs (|G|={hyb['group_order']}, "
          f"tier={hyb['eff_tier']}, modeled live spread "
          f"{LIVE_FRACTION}·[1±{HYBRID_LIVE_SPREAD}]):")
    print(f"  {'term':>6} {'live frac':>10} {'stream ms':>11} "
          f"{'recompute ms':>13}  tier")
    for t in range(hyb["num_terms"]):
        side = "stream" if hyb["stream_mask"][t] else "recompute"
        print(f"  {t:>6} {hyb['live_frac'][t]:>10.3f} "
              f"{hyb['stream_ms'][t]:>11.3f} "
              f"{hyb['recompute_ms'][t]:>13.3f}  {side}")
    print(f"  -> {hyb['stream_terms']}/{hyb['num_terms']} terms streamed "
          f"({hyb['stream_term_fraction']:.0%}; "
          f"{hyb['stream_live_fraction']:.0%} of the live entries)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    src = ap.add_argument_group("input (one of)")
    src.add_argument("--snapshot", metavar="RUN",
                     help="obs run dir or .jsonl with memory_ledger events")
    src.add_argument("--structure", metavar="PATH",
                     help="engine structure sidecar (*.structure.h5)")
    src.add_argument("--n-states", type=float, default=None)
    ap.add_argument("--num-terms", type=int, default=None,
                    help="off-diagonal terms T (explicit-parameter mode)")
    ap.add_argument("--t0", type=int, default=None,
                    help="packed main-table width T0 (default: num-terms)")
    ap.add_argument("--pair", action="store_true",
                    help="(re, im)-f64 pair sector (16 B coefficients)")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="device memory budget in GB (default 16)")
    ap.add_argument("--host-ram-gb", type=float, default=64.0,
                    help="host RAM budget per rank in GB for the streamed "
                         "mode's resolved plan (default 64; the disk tier "
                         "extends it when the artifact cache is on)")
    ap.add_argument("--utilization", type=float,
                    default=DEFAULT_UTILIZATION,
                    help="usable fraction of HBM (default 0.85)")
    ap.add_argument("--n-devices", type=int, default=1)
    ap.add_argument("--vectors", type=int, default=3,
                    help="live full-length vectors to budget (default 3)")
    ap.add_argument("--vec-width", type=int, default=1,
                    help="RHS columns per vector (multi-RHS batches)")
    ap.add_argument("--target-n", type=float, default=None,
                    help="recommend mode/shards for this basis size")
    ap.add_argument("--stream-compress",
                    choices=STREAM_COMPRESS_SETTINGS,
                    default=os.environ.get("DMT_STREAM_COMPRESS", "off"),
                    help="streamed-plan codec setting to size the host "
                         "plan (and its est ms/apply) at; every "
                         "setting's bytes/row is reported alongside "
                         "(default: DMT_STREAM_COMPRESS or off)")
    ap.add_argument("--group-order", type=int, default=1, metavar="G",
                    help="symmetry group order |G| for the hybrid "
                         "recompute pricing (default 1 — unprojected "
                         "sectors, the cheap-orbit regime)")
    ap.add_argument("--hybrid", action="store_true",
                    help="print the per-term recompute-vs-stream cost "
                         "table the hybrid split is priced from "
                         "(DESIGN.md §28; needs a rate calibration)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="rate-calibration JSON from tools/gather_bound.py "
                         "(default: the content-addressed sidecar under "
                         "the artifact root, when present) — adds "
                         "gather/stream-bound est_apply_ms per mode")
    ap.add_argument("--tuning", nargs="?", const="auto", default=None,
                    metavar="auto|off",
                    help="fold the tune/ subsystem in (DESIGN.md §30): "
                         "price at the live posterior's LEARNED rates "
                         "when one has been persisted, and surface the "
                         "saved tuned configs as rows the recommendation "
                         "prefers over the catalog when they price "
                         "better (run with tune=static to adopt one)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    measured = None
    if args.snapshot:
        snap = load_snapshot(args.snapshot)
        led = snap["ledger"]
        measured = {k: led.get(k) for k in
                    ("mode", "n_states", "n_padded", "shard_size",
                     "n_devices", "T0", "table_bytes", "num_terms", "pair",
                     "plan_bytes", "plan_bytes_raw", "stream_compress")}
        for key in ("plan_bytes", "plan_bytes_raw"):
            if measured.get(key):
                # a rank's ledger reports its OWN shards' plan bytes; the
                # per-row calibration divides by the GLOBAL padded row
                # count, so scale to the whole job (envelopes carry
                # n_ranks)
                measured[key] = int(measured[key]) \
                    * int(led.get("n_ranks", 1) or 1)
        if measured.get("n_padded") is None and led.get("shard_size"):
            measured["n_padded"] = int(led["shard_size"]) \
                * int(led.get("n_devices", 1))
        n_states = int(led["n_states"])
        num_terms = int(led.get("num_terms") or args.num_terms or 1)
        T0 = int(led.get("T0") or args.t0 or num_terms)
        pair = bool(led.get("pair")) or args.pair
        n_devices = args.n_devices if args.n_devices != 1 \
            else int(led.get("n_devices") or 1)
    elif args.structure:
        st = load_structure(args.structure)
        measured = st
        n_states = int(args.n_states or st["n_states"])
        num_terms = int(args.num_terms or st["T0"])
        T0 = int(args.t0 or st["T0"])
        pair = st["pair"] or args.pair
        n_devices = args.n_devices
    else:
        if args.n_states is None or args.num_terms is None:
            ap.error("pass --snapshot, --structure, or both "
                     "--n-states and --num-terms")
        n_states = int(args.n_states)
        num_terms = int(args.num_terms)
        T0 = int(args.t0 or num_terms)
        pair = args.pair
        n_devices = args.n_devices

    rates = load_rate_calibration(args.calibration)
    tuning = None
    if args.tuning and args.tuning != "off":
        tuning = load_tuning()
        if tuning and tuning.get("rates"):
            # the streamed posterior is the broadest phase mix; any
            # posterior beats the static catalog for pricing
            post = tuning["rates"].get("streamed") \
                or next(iter(tuning["rates"].values()), None)
            if post:
                rates = post
        if tuning is None:
            print("  --tuning: no posterior or tuned-config artifacts "
                  "found (run an engine with DMT_TUNE=static|live first)",
                  file=sys.stderr)
    report = plan(n_states, num_terms, T0, pair, args.hbm_gb, n_devices,
                  args.vectors, args.vec_width, measured=measured,
                  utilization=args.utilization,
                  host_ram_gb=args.host_ram_gb,
                  rates=rates,
                  stream_compress=args.stream_compress,
                  group_order=args.group_order)
    if tuning:
        report["tuning"] = tuning_report(tuning, rates)
    rec = recommend(report, int(args.target_n) if args.target_n else None)
    if args.json:
        print(json.dumps({"report": report, "recommendation": rec},
                         indent=1, sort_keys=True))
    else:
        print_report(report, rec)
        if args.hybrid:
            hyb_tier = "lossless" if args.stream_compress == "off" \
                else args.stream_compress
            print_hybrid_terms(report, hybrid_split_model(
                n_states, num_terms, pair, n_devices, args.group_order,
                rates, hyb_tier))
    return 0


if __name__ == "__main__":
    sys.exit(main())
