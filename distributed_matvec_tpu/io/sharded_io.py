"""Chunked / per-shard vector I/O for the ≥10⁹-state regime.

The reference reads and writes big datasets in hyperslab chunks and
per-locale blocks (``MyHDF5.chpl:105-162, 272-333``) because no locale can
hold a global array.  The analogs here:

* :func:`stream_block_to_shards` — a block-order (global sorted) dataset,
  e.g. a golden ``/x`` next to ``/representatives``
  (input_for_matvec.py:28-46), is read in hyperslab chunks, hash-routed
  (``localeIdxOf``), and appended to per-shard datasets.  Chunks ascend and
  block order is ascending-state order, so each shard's stream lands in
  exactly the per-shard sorted order the engine consumes — this is
  ``arrFromBlockToHashed`` (BlockToHashed.chpl:87-208) as streaming I/O,
  with bounded memory.
* :func:`save_hashed_vector` / :func:`load_hashed_shard` — a hashed
  ``[D, M(, k)]`` array (eigenvectors, checkpoint state) written one shard
  at a time with the pad rows stripped, and read back per shard (the
  per-locale block read of ``readDatasetAsBlocks``, MyHDF5.chpl:272-286).
  In a multi-process run each process writes/reads only its addressable
  shards.

Shard-aligned vector files carry the counts they were written with, so a
consumer can assemble the padded ``[D, M]`` device array directly (see
``DistributedEngine.from_shards`` for the representative-side analog).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import numpy as np

from ..enumeration.host import shard_index

__all__ = ["stream_block_to_shards", "save_hashed_vector",
           "save_hashed_vectors", "load_hashed_shard",
           "load_hashed_meta", "hashed_vector_counts",
           "hashed_shard_reader"]

_CHUNK = 1 << 20


def stream_block_to_shards(src_path: str, out_path: str, n_shards: int,
                           x_dataset: str = "x",
                           reps_dataset: str = "representatives",
                           name: str = "v",
                           chunk: int = _CHUNK) -> np.ndarray:
    """Route a block-order dataset into per-shard datasets, chunk by chunk.

    ``src_path[x_dataset]`` may be rank-1 [N] or a batch [k, N] (the golden
    generator's transposed layout, input_for_matvec.py:43-46); the output
    shard datasets are [c_d] or [c_d, k].  Returns the per-shard counts.
    """
    import h5py

    with h5py.File(src_path, "r") as fin, h5py.File(out_path, "w") as fout:
        reps = fin[reps_dataset]
        xd = fin[x_dataset]
        batch = xd.ndim == 2
        n = reps.shape[0]
        if (xd.shape[-1] if batch else xd.shape[0]) != n:
            raise ValueError(
                f"{x_dataset} has {xd.shape} entries for {n} representatives")
        counts = np.zeros(n_shards, np.int64)
        g = fout.create_group(f"vector_shards/{name}")
        dsets = []
        for d in range(n_shards):
            shape = (0, xd.shape[0]) if batch else (0,)
            maxshape = (None, xd.shape[0]) if batch else (None,)
            chunks = (min(chunk, _CHUNK),) + ((xd.shape[0],) if batch else ())
            dsets.append(g.create_dataset(str(d), shape=shape,
                                          maxshape=maxshape, dtype=xd.dtype,
                                          chunks=chunks))
        for s in range(0, n, chunk):
            e = min(s + chunk, n)
            r_c = reps[s:e]
            x_c = xd[:, s:e].T if batch else xd[s:e]
            owner = shard_index(np.asarray(r_c, np.uint64), n_shards)
            order = np.argsort(owner, kind="stable")
            x_s = x_c[order]
            bounds = np.searchsorted(owner[order], np.arange(n_shards + 1))
            for d in range(n_shards):
                lo, hi = bounds[d], bounds[d + 1]
                if lo == hi:
                    continue
                ds = dsets[d]
                o = ds.shape[0]
                ds.resize((o + hi - lo,) + ds.shape[1:])
                ds[o:] = x_s[lo:hi]
                counts[d] += hi - lo
        fout.attrs["counts"] = counts
        fout.attrs["n_shards"] = n_shards
    return counts


def save_hashed_vector(path: str, xh, counts, name: str = "v") -> None:
    """Write a hashed ``[D, M(, k)]`` array one shard at a time, pad rows
    stripped; only shards addressable by this process are written (pass the
    same ``counts`` the layout/manifest carries).

    HDF5 has no concurrent-writer support, so in a multi-process run each
    rank writes its OWN file (``path.r<rank>``); :func:`load_hashed_shard`
    finds a shard in whichever file holds it."""
    save_hashed_vectors(path, {name: xh}, counts)


def save_hashed_vectors(path: str, vectors: dict, counts,
                        meta: Optional[dict] = None) -> None:
    """Write several named hashed arrays in ONE atomic file pass — the
    rewrite cost is paid once, not once per vector (a k-eigenvector save
    would otherwise re-copy all earlier vectors k times).

    Atomic write (matching save_engine_structure / enumerate_to_shards):
    the whole file is built at a temp path and ``os.replace``d, so a crash
    mid-save can't leave a corrupt or mixed-generation vector file, and
    each rewritten group is recreated wholesale so stale shard datasets
    from an earlier save with a different D/counts can't survive.  All
    other file content (other vector groups, co-located datasets/groups,
    root attrs) is carried over; an unreadable previous file is an error —
    silently replacing it would destroy co-located data the caller never
    asked us to touch.

    ``meta`` (scalars/small arrays) is written under ``/ckpt_meta`` in the
    SAME atomic pass, replacing any previous meta — so checkpoint metadata
    and the vectors it describes can never be of mixed generations (see
    solve/lanczos.py's multi-process checkpoint)."""
    import os
    import tempfile

    import h5py
    import jax

    from ..utils import faults

    counts = np.asarray(counts, np.int64)
    D = counts.size
    if jax.process_count() > 1:
        path = f"{path}.r{jax.process_index()}"
    faults.check("ckpt_write", path=path)
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(os.path.abspath(path)) or ".", suffix=".tmp")
    os.close(fd)
    try:
        with h5py.File(tmp, "w") as fout:
            if os.path.exists(path):
                with h5py.File(path, "r") as fin:
                    for k in fin:
                        if k == "vector_shards":
                            dst = fout.require_group("vector_shards")
                            for other in fin["vector_shards"]:
                                if other not in vectors:
                                    fin.copy(f"vector_shards/{other}", dst,
                                             name=other)
                        elif k == "ckpt_meta" and meta is not None:
                            pass             # replaced wholesale below
                        else:
                            fin.copy(k, fout, name=k)
                    for k, v in fin.attrs.items():
                        if k not in ("counts", "n_shards"):
                            fout.attrs[k] = v
            for name, xh in vectors.items():
                g = fout.require_group(f"vector_shards/{name}")
                for d in range(D):
                    shard = None
                    if isinstance(xh, dict):
                        # pre-fetched host pieces {d: rows} — lets callers
                        # stage one device row at a time (solve/lanczos.py)
                        shard = xh.get(d)
                    elif isinstance(xh, jax.Array):
                        for piece in xh.addressable_shards:
                            if piece.index[0].start == d:
                                shard = np.asarray(piece.data)[0]
                                break
                    else:
                        shard = np.asarray(xh)[d]
                    if shard is None:
                        continue            # another process's shard
                    g.create_dataset(str(d), data=shard[: counts[d]])
            if meta is not None:
                g = fout.require_group("ckpt_meta")
                for k, v in meta.items():
                    if isinstance(v, str):
                        g.attrs[k] = v      # h5py rejects numpy str scalars
                        continue
                    a = np.asarray(v)
                    if a.ndim == 0:
                        g.attrs[k] = a[()]
                    else:
                        g.create_dataset(k, data=a)
            fout.attrs["counts"] = counts
            fout.attrs["n_shards"] = D
        faults.check("ckpt_rename", path=path)
        os.replace(tmp, path)
        from ..utils.artifacts import note_artifact_ok

        note_artifact_ok(path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def load_hashed_meta(path: str,
                     expected_fingerprint: Optional[str] = None
                     ) -> Optional[dict]:
    """The ``/ckpt_meta`` group of a hashed-vector file (attrs + datasets),
    searched across ``path`` and any per-rank ``path.r*`` files; None when
    absent.

    ``expected_fingerprint`` keeps the scan going past candidates whose
    ``fingerprint`` attr doesn't match — without it, a stale base-path file
    left by an earlier single-process run would mask valid per-rank ``.r*``
    checkpoints and a resume would silently start fresh."""
    import glob
    import h5py

    for cand in [path] + sorted(glob.glob(f"{path}.r*")):
        try:
            with h5py.File(cand, "r") as f:
                if "ckpt_meta" not in f:
                    continue
                if not _fingerprint_ok(f, expected_fingerprint):
                    continue
                g = f["ckpt_meta"]
                out = {k: g.attrs[k] for k in g.attrs}
                for k in g:
                    out[k] = g[k][...]
                return out
        except OSError:
            continue
    return None


def _fingerprint_ok(f, expected_fingerprint: Optional[str]) -> bool:
    """True when ``expected_fingerprint`` is unset or matches the file's
    ``/ckpt_meta`` fingerprint attr — the filter that keeps a stale
    base-path file from an earlier run from shadowing valid per-rank
    ``.r*`` files in the scans below."""
    if expected_fingerprint is None:
        return True
    if "ckpt_meta" not in f:
        return False
    return (str(f["ckpt_meta"].attrs.get("fingerprint", ""))
            == expected_fingerprint)


def _generation_ok(f, match_meta: Optional[dict]) -> bool:
    """True when the file's own ``/ckpt_meta`` generation scalars
    (``m``, ``total_iters``) agree with the checkpoint metadata the
    caller already selected.  Per-rank ``.r*`` files are written without
    a barrier, so a crash between rank saves leaves files of MIXED
    generations that all pass the fingerprint filter — and a thick
    restart SHRINKS ``m``, so a stale file can satisfy every shard fetch
    of a newer, smaller checkpoint.  Fetching from such a file would
    silently splice old basis rows into the resumed solve."""
    if match_meta is None:
        return True
    if "ckpt_meta" not in f:
        return False
    attrs = f["ckpt_meta"].attrs
    for k in ("m", "total_iters"):
        if k not in match_meta:
            continue
        if k not in attrs or int(attrs[k]) != int(match_meta[k]):
            return False
    return True


@contextlib.contextmanager
def hashed_shard_reader(path: str,
                        expected_fingerprint: Optional[str] = None,
                        match_meta: Optional[dict] = None):
    """Scan-once, open-once shard reader over ``path`` and its per-rank
    ``path.r*`` files.  Candidates are globbed, opened, and filtered ONE
    time — by ``expected_fingerprint`` (the stale-file filter of
    :func:`load_hashed_shard`) AND by generation agreement of each
    file's own ``/ckpt_meta`` against ``match_meta``, the metadata the
    caller already selected — then the yielded ``fetch(d, name)`` serves
    every per-(row, shard) read from the already-open files.

    A checkpoint restore reads O(m·D) shard slices; per-call
    :func:`load_hashed_shard` scans would bill ~m·D glob+open+close
    cycles to the restore's ``reshard_s``.  The generation
    filter is a correctness matter, not an optimization: barrier-free
    per-rank saves mean mixed-generation ``.r*`` files can coexist under
    one fingerprint, and a fetch that fell through to a stale file would
    splice rows of a different Krylov basis into the resume.  A shard
    absent from every same-generation file raises ``KeyError`` — the
    caller's existing incomplete-checkpoint degrade path."""
    import glob

    import h5py

    files = []
    try:
        for cand in [path] + sorted(glob.glob(f"{path}.r*")):
            try:
                f = h5py.File(cand, "r")
            except OSError:
                continue
            if (_fingerprint_ok(f, expected_fingerprint)
                    and _generation_ok(f, match_meta)):
                files.append(f)
            else:
                f.close()

        def fetch(d: int, name: str = "v") -> np.ndarray:
            key = f"vector_shards/{name}"
            sd = str(d)
            for f in files:
                if key in f and sd in f[key]:
                    return f[key][sd][...]
            raise KeyError(
                f"shard {d} of {name!r} not found under {path}(.r*) in "
                "the restored checkpoint generation")

        yield fetch
    finally:
        for f in files:
            f.close()


def load_hashed_shard(path: str, d: int, name: str = "v",
                      expected_fingerprint: Optional[str] = None
                      ) -> np.ndarray:
    """One shard's rows of a saved hashed vector (pad rows NOT included).
    Looks in ``path`` first, then in any per-rank ``path.r*`` files a
    multi-process save produced; ``expected_fingerprint`` skips files whose
    ``/ckpt_meta`` fingerprint differs (checkpoint consumers MUST pass it —
    otherwise a stale base-path file shadows the valid per-rank data its
    metadata was already fingerprint-matched against)."""
    import glob
    import h5py

    key = f"vector_shards/{name}"
    for cand in [path] + sorted(glob.glob(f"{path}.r*")):
        try:
            with h5py.File(cand, "r") as f:
                if not _fingerprint_ok(f, expected_fingerprint):
                    continue
                if key in f and str(d) in f[key]:
                    return f[key][str(d)][...]
        except OSError:
            continue
    raise KeyError(f"shard {d} of {name!r} not found under {path}(.r*)")


def hashed_vector_counts(path: str,
                         expected_fingerprint: Optional[str] = None
                         ) -> Optional[np.ndarray]:
    """The ``counts`` attr of a hashed-vector file, searched across ``path``
    and any per-rank ``path.r*`` files (a multi-process save writes only to
    ``path.r<rank>``; every rank's file carries the full counts array).
    ``expected_fingerprint`` applies the same stale-file filter as
    :func:`load_hashed_shard`."""
    import glob
    import h5py

    for cand in [path] + sorted(glob.glob(f"{path}.r*")):
        try:
            with h5py.File(cand, "r") as f:
                if not _fingerprint_ok(f, expected_fingerprint):
                    continue
                return np.asarray(f.attrs["counts"], np.int64)
        except (OSError, KeyError):
            continue
    return None
