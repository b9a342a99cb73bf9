#!/usr/bin/env python
"""Pre-warm the artifact caches for a few chain and lattice configs.

Builds, for each selected config, the three construction products the
default-on artifact layer (``distributed_matvec_tpu/utils/artifacts.py``)
checkpoints:

  * basis representatives  (``<root>/basis/``)
  * ELL structure sidecar  (``<root>/structure/``)
  * XLA compiled programs  (``JAX_COMPILATION_CACHE_DIR``, else the
    checkout's ``.cache/xla`` — ``utils/cache.py``)

so the *next* process — the CLI, a driver inside a short accelerator
window — constructs its engines in seconds instead of minutes
(``make warm-cache``).  Prints one JSON line per config with the cold/warm
signal: ``basis_restored``/``structure_restored`` are False on the run that
fills the cache and True on every run after it.

Usage::

    python tools/warm_cache.py --configs smoke   # chain_16 only (CI-fast)
    python tools/warm_cache.py --configs cpu     # the CPU-feasible matrix
    python tools/warm_cache.py --configs full    # + chain_32_symm (slow)
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _chain_symm(n):
    """The fully symmetric sector of an ``n``-site ring (the basis of
    ``data/heisenberg_chain_32_symm.yaml`` at ``n`` = 32)."""
    return dict(number_spins=n, hamming_weight=n // 2, spin_inversion=1,
                symmetries=[([*range(1, n), 0], 0),
                            ([*reversed(range(n))], 0)])


def _configs(which):
    smoke = [("chain_16", dict(number_spins=16, hamming_weight=8), None)]
    if which == "smoke":
        return smoke
    from distributed_matvec_tpu.models.lattices import (kagome_16_edges,
                                                        square_edges)
    cpu = smoke + [
        ("chain_20", dict(number_spins=20, hamming_weight=10), None),
        ("kagome_16", dict(number_spins=16, hamming_weight=8),
         kagome_16_edges()),
        ("square_4x4", dict(number_spins=16, hamming_weight=8),
         square_edges(4, 4)),
        ("chain_24_symm", _chain_symm(24), None),
    ]
    if which == "cpu":
        return cpu
    return cpu + [("chain_32_symm", _chain_symm(32), None)]


def warm_one(name, basis_args, edges):
    import jax

    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.utils.artifacts import make_or_restore_basis

    op = heisenberg_from_edges(
        SpinBasis(**basis_args),
        edges if edges is not None
        else chain_edges(basis_args["number_spins"]))
    basis_restored = make_or_restore_basis(op.basis)
    eng = LocalEngine(op, mode="ell")          # default artifact cache
    # one apply so the matvec program lands in the XLA cache too
    x = jax.numpy.zeros(op.basis.number_states).at[0].set(1.0)
    jax.block_until_ready(eng._matvec(x)[0])
    return {
        "config": name,
        "n_states": op.basis.number_states,
        "basis_restored": bool(basis_restored),
        "structure_restored": bool(eng.structure_restored),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--configs", choices=("smoke", "cpu", "full"),
                    default="cpu")
    ap.add_argument("--artifact-dir", default=None,
                    help="override the artifact root (DMT_ARTIFACT_DIR)")
    args = ap.parse_args()
    if args.artifact_dir:
        os.environ["DMT_ARTIFACT_DIR"] = args.artifact_dir
    os.environ["DMT_ARTIFACT_CACHE"] = "on"      # force the layer on

    from distributed_matvec_tpu.utils.artifacts import (artifact_root,
                                                        ensure_compilation_cache)
    ensure_compilation_cache()
    print(json.dumps({"artifact_root": artifact_root()}), flush=True)
    failures = 0
    for name, basis_args, edges in _configs(args.configs):
        try:
            print(json.dumps(warm_one(name, basis_args, edges)), flush=True)
        except Exception as e:                      # keep warming the rest
            failures += 1
            print(json.dumps({"config": name, "error": repr(e)}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
