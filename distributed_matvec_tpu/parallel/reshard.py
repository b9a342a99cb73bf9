"""Topology-portable checkpoint resharding: D source shards → D′ devices.

PR 6 made solves preemption-safe on a FIXED device count: a checkpoint
written at D devices stores each Krylov/LOBPCG row as per-shard slices of
the hash partition ``shard_index(σ, D)`` and can only be restored onto a
mesh of exactly D devices.  Production fleets shrink and grow — losing one
host of a spot slice must not orphan a multi-hour solve.

The partition is *deterministic*: state σ lives on shard
``hash64(σ) % D`` (``localeIdxOf``, StatesEnumeration.chpl:129-136), and
within a shard rows sit in ascending state order.  So redistribution from
D to D′ is a **computable permutation** — no solver state is approximate
or lost — and restore becomes

1. **gather-from-source-shards**: target device ``p`` hosts the saved
   slices of source shards ``{s : s ≡ p (mod D′)}`` as one zero-padded
   slab (each slice read straight from the checkpoint file(s); in a
   multi-controller run the per-rank ``path.r*`` files of the OLD
   topology are all scanned, so shards written by departed ranks are
   found on the shared filesystem), then
2. **staged redistribution**: one ``shard_map`` program gathers each
   slab entry into its destination bucket, exchanges the buckets with
   the ``ppermute``-round decomposition of
   :func:`~.distributed._staged_all_to_all` (the portable-collective
   schedule of "Memory-efficient array redistribution", PAPERS.md), and
   scatters every received entry into its target row slot.

Following GSPMD's one-static-program argument (PAPERS.md), the routing
(send indices, receive slots, capacities) is resolved on the host ONCE
per (D, D′) pair and the exchange program is compiled once; all m+1
checkpointed rows then stream through the same executable.

The checkpoint's **topology stanza** (written by
``solve/lanczos.py``/``lobpcg`` into ``ckpt_meta``) carries everything
needed to decide and verify a reshard::

    ckpt_version     2
    topology_d       D the snapshot was written at
    topology_m       padded shard size at D
    topology_counts  per-shard real-row counts [D]
    partition_fp     :func:`partition_fingerprint` of the hash partition

A restore at D′ ≠ D reshards; a ``partition_fp`` mismatch (someone
changed the shard hash — the snapshots are NOT a permutation of the new
partition) raises :class:`PartitionMismatch` with a pointer at the cause
instead of silently restoring garbage.  The ``ckpt_reshard`` fault site
(``DMT_FAULT=ckpt_reshard``) injects a torn reshard so the chaos gate can
assert the degrade path: the solve starts fresh, it never resumes from a
half-redistributed basis.
"""

from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from ..enumeration.host import hash64, shard_index
from ..utils import faults

__all__ = ["PartitionMismatch", "partition_fingerprint", "topology_stanza",
           "hashed_ckpt_engine", "Resharder"]


class PartitionMismatch(ValueError):
    """The checkpoint's shard partition is not the one this build
    computes — resharding would scatter rows to wrong owners, so the
    restore must refuse (the caller degrades to a fresh solve)."""


def partition_fingerprint() -> str:
    """Content fingerprint of the hash partition itself: the splitmix64
    finalizer evaluated on a fixed probe, digested.  Any change to the
    hash function or its seed changes this string, so a checkpoint
    written under a different partition is refused with a pointer at the
    cause instead of being reshuffled into garbage (reshard math assumes
    the SAME per-state owner function at both topologies)."""
    import hashlib

    probe = hash64(np.arange(16, dtype=np.uint64))
    return "splitmix64:" + hashlib.sha256(probe.tobytes()).hexdigest()[:16]


def hashed_ckpt_engine(owner) -> bool:
    """True when ``owner`` is an engine exposing the hashed ``[D, M]``
    shard layout a topology-portable checkpoint needs (counts, shard
    size, per-shard assembly)."""
    return (owner is not None
            and hasattr(owner, "counts")
            and hasattr(owner, "shard_size")
            and hasattr(owner, "_assemble_sharded"))


def topology_stanza(owner) -> dict:
    """The checkpoint-metadata topology stanza for an engine-backed save
    (empty for bare callables / engines without a hashed layout — those
    checkpoints stay fixed-topology by construction)."""
    if not hashed_ckpt_engine(owner):
        return {}
    return {"ckpt_version": 2,
            "topology_d": int(owner.n_devices),
            "topology_m": int(owner.shard_size),
            "topology_counts": np.asarray(owner.counts, np.int64),
            "partition_fp": partition_fingerprint()}


def _global_states(owner) -> np.ndarray:
    """The sorted global state array the routing is computed from.

    Preference order: the built basis' representatives; the sharded
    enumeration file (shard-native engines — the global array is
    materialized HERE only, O(N) host memory, the same documented
    trade-off as ``DistributedEngine._require_layout``); the engine's own
    per-shard sorted rows when every shard is addressable (rank-local
    meshes).
    """
    basis = getattr(getattr(owner, "operator", None), "basis", None)
    if basis is not None and getattr(basis, "is_built", False):
        return np.asarray(basis.representatives, np.uint64)
    if getattr(owner, "_shards_path", None):
        from ..enumeration.sharded import load_shard
        states = np.concatenate(
            [load_shard(owner._shards_path, d)[0]
             for d in range(owner.n_devices)])
        states.sort()
        return states
    if all(owner._shard_addressable(d) for d in range(owner.n_devices)):
        from .engine import SENTINEL_STATE
        pieces = []
        alphas = np.asarray(owner._alphas)
        for d in range(owner.n_devices):
            pieces.append(alphas[d][: int(owner.counts[d])])
        states = np.concatenate(pieces).astype(np.uint64)
        states.sort()
        assert not np.any(states == SENTINEL_STATE)
        return states
    raise PartitionMismatch(
        "resharded restore needs the global state list (built basis, "
        "shards file, or an all-addressable mesh) to recompute the "
        "source partition; none is available on this rank")


class Resharder:
    """Host-resolved D → D′ redistribution plan + its one compiled
    exchange program, reused for every row of a checkpoint.

    ``owner`` is the TARGET engine (D′ = ``owner.n_devices``);
    ``src_d``/``src_counts`` come from the checkpoint's topology stanza;
    ``tail`` is the per-row trailing shape beyond ``[D, M]`` (``()`` for
    real rows, ``(2,)`` for pair vectors, ``(cols,)`` for blocks).
    Raises :class:`PartitionMismatch` when the recomputed source
    partition disagrees with the checkpoint's counts (a different hash
    seed/function — the snapshots are not a permutation of this basis's
    partition).
    """

    def __init__(self, owner, src_d: int, src_counts, tail=()):
        self.owner = owner
        self.src_d = D = int(src_d)
        self.dst_d = Dp = int(owner.n_devices)
        self.tail = tuple(int(t) for t in tail)
        if D < 1:
            raise PartitionMismatch(f"invalid source topology D={D}")
        states = _global_states(owner)
        layout = owner._require_layout()
        if layout.n_shards != Dp or layout.shard_size != owner.shard_size:
            raise PartitionMismatch(
                f"target layout is {layout.n_shards}×{layout.shard_size}, "
                f"engine is {Dp}×{owner.shard_size}")
        owner_src = shard_index(states, D)
        counts_chk = np.bincount(owner_src, minlength=D).astype(np.int64)
        src_counts = np.asarray(src_counts, np.int64)
        if src_counts.size != D or not np.array_equal(counts_chk,
                                                      src_counts):
            raise PartitionMismatch(
                f"checkpoint shard counts {src_counts.tolist()} disagree "
                f"with the partition this build computes "
                f"{counts_chk.tolist()} for D={D} — the checkpoint was "
                "written under a different shard hash (see "
                "partition_fingerprint()); delete the checkpoint or "
                "restore it with the original build")
        # position of each state within its SOURCE shard: states are
        # globally sorted, so the stable rank among equal owners is
        # exactly the per-shard ascending order the save wrote
        n = states.size
        order = np.argsort(owner_src, kind="stable")
        bounds = np.searchsorted(owner_src[order], np.arange(D + 1))
        pos_src = np.empty(n, np.int64)
        pos_src[order] = np.arange(n) - bounds[owner_src[order]]

        # gather-from-source-shards placement: source shard s is hosted
        # on target device s % D′ at slab row s // D′ (zero-padded to the
        # max source count so the slab is rectangular)
        self.slab_rows = -(-D // Dp)
        self.slab_cap = Ms = max(int(src_counts.max(initial=0)), 1)
        Mp = layout.shard_size

        # routing table: every real target slot (q, j) holds global
        # index g, produced by hosting device p at flat slab offset f
        perm = layout.perm
        qq, jj = np.nonzero(perm >= 0)
        g = perm[qq, jj]
        s = owner_src[g].astype(np.int64)
        p = s % Dp
        f = (s // Dp) * Ms + pos_src[g]
        # deterministic bucket order (by destination slot), one bucket
        # per (sender p, receiver q); capacity = the fattest bucket
        o2 = np.lexsort((jj, qq, p))
        p_o, q_o, j_o, f_o = p[o2], qq[o2], jj[o2], f[o2]
        key = p_o * Dp + q_o
        per_bucket = np.bincount(key, minlength=Dp * Dp)
        self.capacity = C = max(int(per_bucket.max(initial=0)), 1)
        starts = np.concatenate(([0], np.cumsum(per_bucket)))
        cpos = np.arange(key.size) - starts[key]
        send_idx = np.full((Dp, Dp, C), -1, np.int64)
        recv_slot = np.full((Dp, Dp, C), -1, np.int64)
        send_idx[p_o, q_o, cpos] = f_o
        recv_slot[q_o, p_o, cpos] = j_o
        self._send_idx_h = send_idx.astype(np.int32)
        self._recv_slot_h = recv_slot.astype(np.int32)
        self._mp = Mp
        self._prog = None
        self._prog_dtype = None
        self._sidx = self._rslot = None

    # -- the one static exchange program per (D, D′) pair ---------------

    def _program(self, dtype):
        """Compile (once) the slab → target-row exchange: static gather
        into per-peer buckets, the staged ``ppermute``-round exchange,
        receive-side scatter into the target slots.  Masked entries
        (slot −1) are routed out of range and dropped — exactly the
        pad-zero invariant the engines rely on."""
        if self._prog is not None and self._prog_dtype == dtype:
            return self._prog
        from jax.sharding import PartitionSpec as P

        from .distributed import _staged_all_to_all
        from .mesh import SHARD_AXIS

        Dp, C, Mp = self.dst_d, self.capacity, self._mp
        tail = self.tail
        flat_n = self.slab_rows * self.slab_cap

        def body(slab, sidx, rslot):
            flat = slab.reshape((flat_n,) + tail)
            idx = jnp.clip(sidx[0], 0, flat_n - 1)
            S = flat[idx]                                  # [Dp, C, *tail]
            mask = (sidx[0] >= 0).reshape((Dp, C) + (1,) * len(tail))
            S = jnp.where(mask, S, 0)
            R = _staged_all_to_all(S, SHARD_AXIS)
            slot = rslot[0].reshape(-1)
            slot = jnp.where(slot >= 0, slot, Mp)          # OOB → dropped
            y = jnp.zeros((Mp,) + tail, S.dtype)
            y = y.at[slot].set(R.reshape((Dp * C,) + tail), mode="drop")
            return y[None]

        nil = [None] * len(tail)
        sm = jax.shard_map(
            body, mesh=self.owner.mesh,
            in_specs=(P(SHARD_AXIS, None, None, *nil),
                      P(SHARD_AXIS, None, None),
                      P(SHARD_AXIS, None, None)),
            out_specs=P(SHARD_AXIS, None, *nil))
        self._prog = jax.jit(sm)
        self._prog_dtype = dtype
        if self._sidx is None:
            self._sidx = self.owner._assemble_sharded(
                [self._send_idx_h[d] for d in range(Dp)])
            self._rslot = self.owner._assemble_sharded(
                [self._recv_slot_h[d] for d in range(Dp)])
        return self._prog

    # -- driving --------------------------------------------------------

    def src_shards_for(self, d: int) -> List[int]:
        """The source shards target device ``d`` hosts in its slab."""
        return [r * self.dst_d + d for r in range(self.slab_rows)
                if r * self.dst_d + d < self.src_d]

    def stage_rows(self, fetch: Callable[[int, int], np.ndarray],
                   n_rows: int, dtype=None):
        """HOST-side staging of ``n_rows`` checkpointed rows: read every
        source-shard slice this rank's devices host and build the
        per-row zero-padded slab pieces.  ``fetch(i, s)`` returns source
        shard ``s``'s real rows (pad stripped) of row ``i``; ``dtype``
        pins the row dtype up front (a rank whose devices host NO source
        shard — the grow direction — must still assemble dtype-consistent
        zero slabs); default: read off the first fetched shard.  Returns
        ``(staged, dtype)`` for :meth:`exchange_rows`.

        Everything that can realistically fail one-sided — file I/O,
        torn source shards, the injected ``ckpt_reshard`` fault (which
        sits at the top so the chaos gate can assert the degrade path) —
        fails HERE, before any cross-process collective is dispatched: a
        process-spanning caller can agree all ranks staged successfully
        and degrade symmetrically, instead of one degraded rank leaving
        its peers deadlocked inside the ppermute rounds.  Host RAM for
        the staged slabs is ~the checkpoint's own size (the same O(rows)
        the fixed-D restore stages), and keeping staging off-device
        means the exchange still streams one slab of HBM at a time."""
        faults.check("ckpt_reshard", exc=OSError,
                     d_from=self.src_d, d_to=self.dst_d, rows=int(n_rows))
        Dp, Ms = self.dst_d, self.slab_cap
        tail = self.tail
        dtype = np.dtype(dtype) if dtype is not None else None
        staged = []
        for i in range(n_rows):
            pieces = [None] * Dp
            for d in range(Dp):
                if not self.owner._shard_addressable(d):
                    continue
                buf = None
                for r, s in enumerate(self.src_shards_for(d)):
                    vals = np.asarray(fetch(i, s))
                    if buf is None:
                        dtype = dtype or vals.dtype
                        buf = np.zeros((self.slab_rows, Ms) + tail, dtype)
                    if vals.shape[1:] != tail or vals.shape[0] > Ms:
                        raise PartitionMismatch(
                            f"source shard {s} row shape {vals.shape} "
                            f"does not fit slab [{Ms}, {tail}]")
                    buf[r, : vals.shape[0]] = vals
                if buf is None:       # grow: device hosts no source shard
                    buf = np.zeros((self.slab_rows, Ms) + tail,
                                   dtype or np.float64)
                pieces[d] = buf
            staged.append(pieces)
        return staged, np.dtype(dtype or np.float64)

    def exchange_rows(self, staged, dtype) -> List[jax.Array]:
        """Run the one static exchange program over staged slab pieces
        (:meth:`stage_rows`'s output), one row in device flight at a
        time.  Returns target-layout ``[D′, M′, *tail]`` device rows.
        This half dispatches the cross-process collectives, so on a
        process-spanning mesh every rank must reach it with the same
        row count — agree on staging success first."""
        prog = self._program(np.dtype(dtype))
        return [prog(self.owner._assemble_sharded(pieces),
                     self._sidx, self._rslot)
                for pieces in staged]

    def reshard_rows(self, fetch: Callable[[int, int], np.ndarray],
                     n_rows: int, dtype=None) -> List[jax.Array]:
        """:meth:`stage_rows` + :meth:`exchange_rows` in one call — the
        single-controller composition (process-spanning callers split
        the halves around a staging agreement; see
        ``solve/lanczos._restore_sharded_rows``)."""
        staged, dt = self.stage_rows(fetch, n_rows, dtype)
        return self.exchange_rows(staged, dt)
