"""Distributed-memory enumeration: representatives stream INTO shards.

The reference enumerates representatives *into* distributed memory — per-chunk
locale masks/counts, a count-matrix exchange, then a counting-sort scatter
with one PUT per destination locale (StatesEnumeration.chpl:305-514); no node
ever holds the global array.  This module is the single-host analog with the
same memory property: the native enumeration kernel streams survivor slabs
(bounded buffers), each slab is hash-routed to its owning shard
(``localeIdxOf``, StatesEnumeration.chpl:129-136) and appended to that
shard's on-disk dataset.  Peak memory is one slab + the append buffers —
never the global representative array — which is what makes the ≥10⁹-state
regime (README.md:69-116) reachable: chain_40_symm's 862M representatives
(13.8 GB of state+norm data) spill to disk while the Python process stays
flat.

Because the enumeration ranges are disjoint and ascending, each shard's
dataset is automatically SORTED — exactly the per-shard order
:class:`~..parallel.shuffle.HashedLayout` produces, so the shards can feed a
:class:`~..parallel.distributed.DistributedEngine` directly.

The shard file doubles as a checkpoint (the ``makeBasisStates`` restore
semantics, Diagonalize.chpl:227-246, one level down): re-running with the
same parameters restores instead of re-enumerating.  Totals are validated
against :meth:`SymmetryGroup.sector_dimension_census` — a pure-combinatorics
count (projector trace over the fixed-hamming space) sharing nothing with
the enumeration kernels.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Optional

import numpy as np

from . import native as _native
from .host import shard_index
from ..utils.logging import log_debug

__all__ = ["enumerate_to_shards", "load_shard", "shard_manifest",
           "finalize_shard_parts", "reshard_shards"]

_CHUNK = 1 << 20     # h5py append granularity (8 MB of u64)


def _fingerprint(n_sites, hamming_weight, group, n_shards,
                 norm_tol) -> str:
    h = hashlib.sha256()
    h.update(json.dumps(
        [n_sites, hamming_weight, n_shards, float(norm_tol)]).encode())
    for p in group.perms:
        h.update(np.asarray(p.perm, np.int64).tobytes())
    h.update(np.ascontiguousarray(group.characters).tobytes())
    h.update(np.ascontiguousarray(group.flip).tobytes())
    return h.hexdigest()


def enumerate_to_shards(
    n_sites: int,
    hamming_weight: Optional[int],
    group,
    n_shards: int,
    path: str,
    norm_tol: float = 1e-12,
    n_chunks: Optional[int] = None,
    n_threads: Optional[int] = None,
    census_check: bool = True,
    flush_elems: int = 4 << 20,
    rank: int = 0,
    n_ranks: int = 1,
) -> dict:
    """Enumerate representatives of the sector straight into per-shard
    datasets at ``path`` (HDF5).  Returns the manifest dict
    ``{"counts": [D], "total": N, "restored": bool}``.

    Requires the native kernel (the pure-NumPy fallback would make the
    ≥10⁸-candidate configs this exists for intractable).

    **Multi-process enumeration** (the analog of the reference's
    per-locale concurrent enumeration, StatesEnumeration.chpl:321-334):
    with ``n_ranks > 1`` this call enumerates only rank ``rank``'s CYCLIC
    set of 64·R equal-index-work chunks (round-robin dealing balances the
    skew of canonical representatives toward small states — see
    ``native.rank_state_ranges``) and writes it to ``path.part<rank>``;
    every rank runs the same call concurrently (separate processes), then
    ONE caller runs :func:`finalize_shard_parts` to census-validate the
    union and write the manifest at ``path``.  Each rank's shard stream is
    internally sorted (its chunks ascend), but ranks interleave in state
    space — :func:`load_shard` merge-sorts the per-rank slices.
    """
    import h5py

    if not (0 <= rank < n_ranks):
        raise ValueError(f"rank {rank} outside 0..{n_ranks - 1}")
    fp = _fingerprint(n_sites, hamming_weight, group, n_shards, norm_tol)
    state_ranges = None
    if n_ranks > 1:
        path = f"{path}.part{rank}"
        fp = f"{fp}|part{rank}/{n_ranks}c64"   # c64 = cyclic-chunk layout
        census_check = False     # only the union can be censused
        state_ranges = _native.rank_state_ranges(
            n_sites, hamming_weight, rank, n_ranks)
    if os.path.exists(path):
        man = shard_manifest(path)
        if man is not None and man.get("fingerprint") == fp:
            log_debug(f"sharded enumeration restored from {path}")
            man["restored"] = True
            return man
        # stale checkpoint: leave it in place until the fresh enumeration
        # SUCCEEDS — os.replace below swaps it atomically, so a crash
        # mid-run preserves the previous (still self-consistent) file

    lib = _native._load()

    D = n_shards
    counts = np.zeros(D, dtype=np.int64)
    pend_s = [[] for _ in range(D)]
    pend_n = [[] for _ in range(D)]
    pending = np.zeros(D, dtype=np.int64)

    tmp = path + ".tmp"
    with h5py.File(tmp, "w") as f:
        g = f.create_group("shards")
        dsets = []
        for d in range(D):
            gd = g.create_group(str(d))
            dsets.append((
                gd.create_dataset("representatives", shape=(0,),
                                  maxshape=(None,), dtype=np.uint64,
                                  chunks=(_CHUNK,)),
                gd.create_dataset("norms", shape=(0,), maxshape=(None,),
                                  dtype=np.float64, chunks=(_CHUNK,)),
            ))

        def flush(d):
            if not pending[d]:
                return
            s = np.concatenate(pend_s[d])
            nn = np.concatenate(pend_n[d])
            ds, dn = dsets[d]
            o = ds.shape[0]
            ds.resize((o + s.size,))
            dn.resize((o + s.size,))
            ds[o:] = s
            dn[o:] = nn
            pend_s[d].clear()
            pend_n[d].clear()
            pending[d] = 0

        done = 0
        slabs = _native._stream_native(
            lib, n_sites, hamming_weight, group,
            n_chunks=n_chunks, n_threads=n_threads, norm_tol=norm_tol,
            batch_tasks=32, state_ranges=state_ranges)
        for slab_s, slab_n in slabs:
            owner = shard_index(slab_s, D)
            # single-pass scatter: stable sort by owner keeps each shard's
            # slice in the slab's (ascending) state order
            order = np.argsort(owner, kind="stable")
            s_sorted = slab_s[order]
            n_sorted = slab_n[order]
            bounds = np.searchsorted(owner[order], np.arange(D + 1))
            for d in range(D):
                lo, hi = bounds[d], bounds[d + 1]
                if lo == hi:
                    continue
                pend_s[d].append(s_sorted[lo:hi])
                pend_n[d].append(n_sorted[lo:hi])
                pending[d] += hi - lo
                counts[d] += hi - lo
                if pending[d] >= flush_elems:
                    flush(d)
            done += slab_s.size
            log_debug(f"sharded enumeration: {done} representatives routed")
        for d in range(D):
            flush(d)

        total = int(counts.sum())
        if census_check:
            want = group.sector_dimension_census(hamming_weight)
            if total != want:
                raise RuntimeError(
                    f"sharded enumeration found {total} representatives but "
                    f"the sector-dimension census says {want} — enumeration "
                    "and combinatorics disagree"
                )
        f.attrs["n_shards"] = D
        f.attrs["counts"] = counts
        f.attrs["total"] = total
        f.attrs["n_sites"] = n_sites
        f.attrs["hamming_weight"] = -1 if hamming_weight is None \
            else int(hamming_weight)
        if n_ranks > 1:
            f.attrs["rank"] = rank
            f.attrs["n_ranks"] = n_ranks
        # fingerprint LAST (same crash-consistency convention as the
        # engine-structure sidecars)
        f.attrs["fingerprint"] = fp
    os.replace(tmp, path)
    log_debug(f"sharded enumeration: {total} representatives in {D} shards "
              f"at {path}")
    return {"counts": counts.tolist(), "total": total, "fingerprint": fp,
            "restored": False}


def finalize_shard_parts(
    n_sites: int,
    hamming_weight: Optional[int],
    group,
    n_shards: int,
    path: str,
    n_ranks: int,
    norm_tol: float = 1e-12,
    census_check: bool = True,
) -> dict:
    """Combine ``n_ranks`` per-rank part files (from
    :func:`enumerate_to_shards` with ``n_ranks > 1``) into a manifest at
    ``path``.  Run by ONE process after every rank's part exists.

    The manifest holds only counts/attrs and the part list — shard data
    stays in the part files; :func:`load_shard` merge-sorts a shard's
    per-rank slices (each internally sorted, interleaved in state space).
    The union total is validated against the sector-dimension census — the
    same independent combinatorial cross-check the single-process path
    runs.
    """
    import h5py

    fp = _fingerprint(n_sites, hamming_weight, group, n_shards, norm_tol)
    man = shard_manifest(path)
    if man is not None and man.get("fingerprint") == fp:
        log_debug(f"sharded enumeration manifest restored from {path}")
        return man
    counts = np.zeros(n_shards, np.int64)
    for r in range(n_ranks):
        pman = shard_manifest(f"{path}.part{r}")
        want_fp = f"{fp}|part{r}/{n_ranks}c64"
        if pman is None or pman.get("fingerprint") != want_fp:
            raise RuntimeError(
                f"part file {path}.part{r} is missing or does not match "
                "this sector/shard-count/rank-split — run every rank's "
                "enumerate_to_shards first"
            )
        counts += np.asarray(pman["counts"], np.int64)
    total = int(counts.sum())
    if census_check:
        want = group.sector_dimension_census(hamming_weight)
        if total != want:
            raise RuntimeError(
                f"union of {n_ranks} enumeration parts holds {total} "
                f"representatives but the sector-dimension census says "
                f"{want} — a part is incomplete or ranks overlapped"
            )
    tmp = path + ".tmp"
    with h5py.File(tmp, "w") as f:
        f.attrs["n_shards"] = n_shards
        f.attrs["counts"] = counts
        f.attrs["total"] = total
        f.attrs["n_sites"] = n_sites
        f.attrs["hamming_weight"] = -1 if hamming_weight is None \
            else int(hamming_weight)
        f.attrs["parts"] = n_ranks
        f.attrs["fingerprint"] = fp
    os.replace(tmp, path)
    log_debug(f"sharded enumeration: combined {n_ranks} parts, {total} "
              f"representatives in {n_shards} shards at {path}")
    return {"counts": counts.tolist(), "total": total, "fingerprint": fp,
            "n_shards": n_shards, "parts": n_ranks, "restored": False}


def shard_manifest(path: str) -> Optional[dict]:
    """Counts/total/fingerprint of a shard file, or None if unreadable."""
    import h5py

    try:
        with h5py.File(path, "r") as f:
            if "fingerprint" not in f.attrs:
                return None
            man = {"counts": list(map(int, f.attrs["counts"])),
                   "total": int(f.attrs["total"]),
                   "n_shards": int(f.attrs["n_shards"]),
                   "fingerprint": str(f.attrs["fingerprint"]),
                   "restored": True}
            if "parts" in f.attrs:
                man["parts"] = int(f.attrs["parts"])
            return man
    except OSError:
        return None


def reshard_shards(src_path: str, dst_path: str, n_shards: int,
                   group=None, norm_tol: float = 1e-12) -> dict:
    """Re-route an existing shard file onto a different shard count.

    The mesh size is baked into a shard file (``hash64(state) % D`` owns a
    state — StatesEnumeration.chpl:129-136), so running the same basis on a
    different device count would otherwise force a full re-enumeration.
    This streams the old shards into a new file instead: new shard ``d``
    collects every state with ``hash64 % n_shards == d`` from each old
    shard and merge-sorts them (old shards are sorted, so the filtered
    streams are too).  When ``n_shards`` divides the old count, old shard
    ``o`` can only feed new shard ``o % n_shards`` — the scan skips the
    rest, halving the I/O for the common 8→4 case.  Peak memory is one old
    shard plus one new shard, never the global array.

    With ``group`` the new file carries the exact fingerprint a direct
    enumeration at ``n_shards`` would (restore-compatible); without it a
    derived ``reshard(<old_fp>, D)`` fingerprint still keys structure
    caches uniquely.  The total is validated against the source manifest.
    """
    import h5py

    man = shard_manifest(src_path)
    if man is None:
        raise ValueError(f"no shard manifest at {src_path}")
    old_D = man["n_shards"]
    with h5py.File(src_path, "r") as f:
        n_sites = int(f.attrs["n_sites"])
        hamming_weight = int(f.attrs["hamming_weight"])
    if hamming_weight < 0:
        hamming_weight = None
    if group is not None:
        # the caller's group is about to be stamped into a fingerprint a
        # direct enumeration would trust — verify it actually IS the
        # source file's sector first (total-vs-manifest below is
        # group-independent and cannot catch a wrong momentum sector)
        want_src = _fingerprint(n_sites, hamming_weight, group, old_D,
                                norm_tol)
        if man["fingerprint"] != want_src:
            raise ValueError(
                "the given symmetry group does not match the source shard "
                f"file at {src_path} (fingerprint mismatch) — pass the "
                "group the file was enumerated with, or omit it to get a "
                "derived reshard fingerprint")
        fp = _fingerprint(n_sites, hamming_weight, group, n_shards, norm_tol)
    else:
        fp = hashlib.sha256(
            f"reshard({man['fingerprint']},{n_shards})".encode()).hexdigest()
    existing = shard_manifest(dst_path)
    if existing is not None and existing.get("fingerprint") == fp:
        log_debug(f"reshard manifest restored from {dst_path}")
        return existing
    counts = np.zeros(n_shards, np.int64)
    tmp = dst_path + ".tmp"
    with h5py.File(tmp, "w") as fout:
        # pass 1: ONE scan of the source — each old shard is read once and
        # its rows appended to the owning new shards' growable datasets
        dsets = []
        for d_new in range(n_shards):
            g = fout.create_group(f"shards/{d_new}")
            dsets.append((
                g.create_dataset("representatives", shape=(0,),
                                 maxshape=(None,), dtype=np.uint64,
                                 chunks=(_CHUNK,)),
                g.create_dataset("norms", shape=(0,), maxshape=(None,),
                                 dtype=np.float64, chunks=(_CHUNK,))))
        for d_old in range(old_D):
            s, w = load_shard(src_path, d_old)
            own = shard_index(s, n_shards)
            order = np.argsort(own, kind="stable")
            bounds = np.searchsorted(own[order], np.arange(n_shards + 1))
            for d_new in range(n_shards):
                lo, hi = bounds[d_new], bounds[d_new + 1]
                if lo == hi:
                    continue
                ds, dn = dsets[d_new]
                o = ds.shape[0]
                ds.resize((o + hi - lo,))
                dn.resize((o + hi - lo,))
                ds[o:] = s[order[lo:hi]]
                dn[o:] = w[order[lo:hi]]
                counts[d_new] += hi - lo
            log_debug(f"reshard: routed old shard {d_old} ({s.size} states)")
        # pass 2: appends from successive old shards interleave in state
        # space — restore each new shard's sorted order (one new shard in
        # memory at a time; old shards were sorted, so this is a k-way
        # merge done as a stable argsort)
        for d_new in range(n_shards):
            ds, dn = dsets[d_new]
            s = ds[...]
            if s.size and not (s[:-1] <= s[1:]).all():
                order = np.argsort(s, kind="stable")
                ds[:] = s[order]
                dn[:] = dn[...][order]
            log_debug(f"reshard: new shard {d_new} holds {s.size} states")
        total = int(counts.sum())
        if total != man["total"]:
            raise RuntimeError(
                f"reshard routed {total} states, source manifest says "
                f"{man['total']} — hash routing disagrees with the source")
        fout.attrs["n_shards"] = n_shards
        fout.attrs["counts"] = counts
        fout.attrs["total"] = total
        fout.attrs["n_sites"] = n_sites
        fout.attrs["hamming_weight"] = -1 if hamming_weight is None \
            else int(hamming_weight)
        fout.attrs["fingerprint"] = fp
    os.replace(tmp, dst_path)
    log_debug(f"reshard: {old_D} → {n_shards} shards at {dst_path}")
    return {"counts": counts.tolist(), "total": total, "fingerprint": fp,
            "n_shards": n_shards, "restored": False}


def load_shard(path: str, d: int):
    """(representatives, norms) of one shard — sorted ascending; only this
    shard's data is read into memory.  For a multi-process manifest the
    shard is the MERGE of the part files' slices: each rank's slice is
    internally sorted (its cyclic chunks ascend), but ranks interleave in
    state space, so a k-way merge (stable argsort over the concatenation)
    restores the global per-shard order."""
    import h5py

    with h5py.File(path, "r") as f:
        if "parts" in f.attrs:
            n_ranks = int(f.attrs["parts"])
        else:
            g = f["shards"][str(d)]
            return g["representatives"][...], g["norms"][...]
    reps, norms = [], []
    for r in range(n_ranks):
        with h5py.File(f"{path}.part{r}", "r") as f:
            g = f["shards"][str(d)]
            reps.append(g["representatives"][...])
            norms.append(g["norms"][...])
    reps = np.concatenate(reps)
    norms = np.concatenate(norms)
    if reps.size and not (reps[:-1] <= reps[1:]).all():
        order = np.argsort(reps, kind="stable")
        reps, norms = reps[order], norms[order]
    return reps, norms
