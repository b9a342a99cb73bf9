"""The window block stops where ω crosses √ε (PR 29): the window program
carries the host tracker's two ω rows, ends itself at the crossing step and
reports how many steps it ran; the host keeps the steps before the crossing
and runs the rest of the same block under the full sweep.  No block is run
twice, no window program of a new length is built, and no kept step has a
host ω at or above the limit."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.obs import health as obs_health
from distributed_matvec_tpu.utils.config import update_config

from test_operator import build_heisenberg

L = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
lanczos = L.lanczos


@pytest.fixture
def clean_obs():
    obs.reset_all()
    yield
    obs.reset_all()


def _local(n_sites, syms=()):
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(n_sites, n_sites // 2, None, list(syms))
    op.basis.build()
    return op, LocalEngine(op, mode="ell")


# ---------------------------------------------------------------------------
# the device's rows are the host's


def _recorded(monkeypatch, limit, **solve):
    """(α, β, mcap) of a real selective solve whose gate reads ``limit``,
    taken off the last call of the host tracker."""
    seen = {}
    advance = L._OmegaTracker.advance

    def recording(self, alph, bet, m_new):
        seen.update(alph=np.array(alph), bet=np.array(bet), m=int(m_new),
                    mcap=self.rows - 1)
        return advance(self, alph, bet, m_new)

    monkeypatch.setattr(L._OmegaTracker, "advance", recording)
    monkeypatch.setattr(obs_health, "OMEGA_WARN", limit)
    op, eng = _local(12)
    res = lanczos(eng.matvec, op.basis.number_states, k=1, tol=1e-10,
                  **solve)
    assert res.converged
    monkeypatch.undo()
    return seen


@pytest.mark.parametrize("limit", [1e-8, 1e-11, 1e-13],
                         ids=["sqrt_eps", "1e-11", "1e-13"])
def test_device_omega_rows_are_the_host_trackers(monkeypatch, limit):
    """``_omega_row`` under ``jax.jit`` with a traced step index against
    ``_OmegaTracker`` over the (α, β) a real solve recorded: every row to
    1e-10 relative, and the same first step at or above the limit."""
    # a gate that never trips leaves the whole solve's (α, β) from window
    # blocks alone: the recurrence the tracker would have followed
    rec = _recorded(monkeypatch, np.inf, seed=3)
    alph, bet, m, mcap = rec["alph"], rec["bet"], rec["m"], rec["mcap"]
    assert m >= 32
    monkeypatch.setattr(obs_health, "OMEGA_WARN", limit)
    host = L._OmegaTracker(mcap)
    step = jax.jit(lambda w, wp, j: L._omega_row(
        jnp, w, wp, jnp.asarray(alph), jnp.asarray(bet), j, host.eps))
    w, wp = jnp.asarray(host.w_curr), jnp.asarray(host.w_prev)
    crossed_at = None
    for j in range(m):
        new, worst = step(w, wp, jnp.int32(j))
        host.advance(alph, bet, j + 1)
        if host.m == j:                  # the host stopped before step j
            crossed_at = j
            assert not float(worst) < limit
            break
        assert float(worst) < limit
        w, wp = new, w
        np.testing.assert_allclose(np.asarray(w), host.w_curr, rtol=1e-10,
                                   atol=0)
        np.testing.assert_allclose(np.asarray(wp), host.w_prev, rtol=1e-10,
                                   atol=0)
    if np.isfinite(limit) and limit < 1e-10:
        assert crossed_at is not None and crossed_at > 1
    # a tracker asked for the whole range at once stops at the same step
    again = L._OmegaTracker(mcap)
    worst = again.advance(alph, bet, m)
    assert again.m == (m if crossed_at is None else crossed_at)
    assert (worst >= limit) == (crossed_at is not None)


def test_reset_keeps_the_true_previous_row_after_one_full_step():
    """Two consecutive fully reorthogonalised vectors make the table
    roundoff; after one, the row before it is the one the tracker held."""
    tr = L._OmegaTracker(8)
    rng = np.random.default_rng(0)
    a, b = rng.standard_normal(8), 1 + rng.random(8)
    tr.advance(a, b, 5)
    row5 = tr.w_curr.copy()
    tr.reset(6)                      # one full-sweep step from row 5
    assert tr.m == 6
    np.testing.assert_array_equal(tr.w_prev, row5)
    np.testing.assert_array_equal(
        tr.w_curr, [tr.eps] * 6 + [1.0] + [0.0] * 2)
    tr.reset(8)                      # two more
    np.testing.assert_array_equal(
        tr.w_prev, [tr.eps] * 7 + [1.0] + [0.0])
    np.testing.assert_array_equal(tr.w_curr, [tr.eps] * 8 + [1.0])


# ---------------------------------------------------------------------------
# a solve whose gate is forced to trip at a chosen step


def _force_crossing(monkeypatch, at):
    """Make the estimate read 1 at the absolute step ``at``, on the host
    and in the window program alike (both call ``_omega_row``)."""
    real = L._omega_row

    def forced(xp, w, wp, alph, bet, j, eps):
        new, worst = real(xp, w, wp, alph, bet, j, eps)
        return new, xp.where(j == at, 1.0, worst)

    monkeypatch.setattr(L, "_omega_row", forced)


def _spy(monkeypatch):
    """Record what the block programs report and what the solve leaves:
    ``runs``, in order, ("window", length, m0, ran) and ("full", asked, m0,
    ran); ``lengths`` of the window programs built; ``basis`` (m, rows of
    the final Krylov buffer)."""
    seen = {"runs": [], "lengths": []}
    make_window, make_block = L._make_window_runner, L._make_block_runner
    combine = L._combine_rows

    def window(mv, mcap, shape, dtype, n_reorth, nsteps, pair=False):
        fn = make_window(mv, mcap, shape, dtype, n_reorth, nsteps, pair=pair)
        seen["lengths"].append(int(nsteps))

        def run(V, alph, bet, m0, *rest):
            out = fn(V, alph, bet, m0, *rest)
            seen["runs"].append(("window", int(nsteps), int(m0),
                                 int(out[3])))
            return out
        return run

    def block(*args, **kw):
        fn = make_block(*args, **kw)

        def run(V, alph, bet, m0, nsteps, operands):
            out = fn(V, alph, bet, m0, nsteps, operands)
            seen["runs"].append(("full", int(nsteps), int(m0), int(out[3])))
            return out
        return run

    def combining(S, Vf):
        if not isinstance(Vf, jax.core.Tracer):      # the epilogue's call
            seen["basis"] = (int(S.shape[0]), np.asarray(Vf))
        return combine(S, Vf)

    monkeypatch.setattr(L, "_make_window_runner", window)
    monkeypatch.setattr(L, "_make_block_runner", block)
    monkeypatch.setattr(L, "_combine_rows", combining)
    return seen


def _worst_overlap(basis, pair):
    """Largest |⟨v_i, v_j⟩|, i ≠ j, over the rows of the final basis that
    the Ritz vectors are made of (after a breakdown the row past them is
    the normalised remains of a zero vector); complex inner products for
    (re, im)-pair rows."""
    m, Vf = basis
    Z = Vf[:m]
    if pair:
        Z = Z.reshape(m, -1, 2)
        Z = Z[..., 0] + 1j * Z[..., 1]
    G = Z.conj() @ Z.T
    np.testing.assert_allclose(np.abs(np.diag(G)), 1.0, atol=1e-12)
    return float(np.max(np.abs(G - np.diag(np.diag(G)))))


def _check_forced_solve(monkeypatch, matvec, check_every, at, pair=False,
                        rtol=1e-10, **solve):
    """Run the solve with the gate forced at absolute step ``at`` and hold
    it to what PR 29 promises; returns the events of the forced trip."""
    full = lanczos(matvec, reorth="full", check_every=check_every, **solve)
    assert full.converged
    seen = _spy(monkeypatch)
    _force_crossing(monkeypatch, at)
    obs.reset_all()
    res = lanczos(matvec, reorth="selective", check_every=check_every,
                  compute_eigenvectors=True, **solve)
    assert res.converged
    np.testing.assert_allclose(res.eigenvalues, full.eigenvalues, rtol=rtol)

    spans = obs.events("span")
    root = [e for e in spans if e["name"] == "lanczos"][-1]
    iterations = [e for e in spans if e["name"] == "iteration"]
    trips = [e for e in obs.events("solver_health")
             if e.get("check") == "selective_reorth_fallback"]
    # no window program of a new length: the block's own length, nothing
    # cut to the steps that were kept or left
    assert seen["lengths"] == [check_every]
    # the counts say what ran: what the programs themselves report
    runs = seen["runs"]
    assert all(asked == ran for kind, asked, _, ran in runs
               if kind == "full")
    assert root["steps_run"] == sum(ran for *_, ran in runs)
    assert root["steps_counted"] == res.num_iters
    assert root["steps_run"] == root["steps_counted"] \
        + root["steps_discarded"]
    assert root["omega_stops"] == sum(
        ran < length for kind, length, _, ran in runs if kind == "window")
    assert root["programs_built"] == 2
    # every stopped block: its head kept, the rest of it (and no more) under
    # the full sweep in the very next program, marked ``redo``
    redone = [it for it in iterations if it.get("redo")]
    assert len(redone) == len(trips) >= 1
    for trip, it in zip(trips, redone):
        before = iterations[iterations.index(it) - 1]
        assert not before.get("redo") and before["steps"] == check_every
        assert it["iter"] == before["iter"] + trip["step"]
        assert it["steps"] == check_every - trip["step"]
        assert trip["iter"] == before["iter"] + check_every
        assert trip["omega"] >= obs_health.OMEGA_WARN
    stopped, discarded, forced = 0, 0, []
    for (kind, length, m0, ran), nxt in zip(runs, runs[1:] + [None]):
        if kind != "window":
            continue
        rest = nxt is not None and nxt[0] == "full" \
            and m0 <= nxt[2] < m0 + length
        assert rest or ran == length
        if rest:
            kept = nxt[2] - m0
            assert kept <= ran and nxt[1] == length - kept
            stopped += 1
            discarded += ran - kept
        if m0 <= at < m0 + length:
            # the forced trip: the device stopped at that very step, one
            # step was thrown away, and the steps before it were kept
            assert rest and ran == at - m0 + 1 and kept == at - m0
            forced.append((length, m0, ran))
    assert stopped == len(trips) and forced
    assert root["steps_discarded"] == discarded
    # semiorthogonality of everything that was kept
    assert _worst_overlap(seen["basis"], pair) < 1e-8
    return root, trips, forced


@pytest.mark.parametrize("step", [0, 7, 15],
                         ids=["block_start", "mid_block", "block_end"])
def test_forced_trip_keeps_the_head_and_sweeps_the_rest(
        clean_obs, monkeypatch, step):
    """16-site ring, the gate forced in the second block of 16 at its first
    step, in its middle and at its last step."""
    op, eng = _local(16)
    root, trips, forced = _check_forced_solve(
        monkeypatch, eng.matvec, 16, 16 + step,
        n=op.basis.number_states, k=1, tol=1e-10, max_iters=200)
    assert trips[0]["step"] == step and trips[0]["iter"] == 32
    assert forced == [(16, 16, step + 1)]
    # a stop at the block's last step saves no apply and is no early end
    assert (root["omega_stops"] >= 1) or step == 15


def test_forced_trip_in_a_pair_sector(clean_obs, monkeypatch):
    """The J-aware window (complex momentum sector as (re, im) pairs)."""
    update_config(complex_pair="on")
    try:
        op, eng = _local(10, syms=[([*range(1, 10), 0], 1)])
        assert eng.pair and not op.effective_is_real
        _check_forced_solve(monkeypatch, eng.matvec, 16, 16 + 1, pair=True,
                            n=op.basis.number_states, k=1, tol=1e-10,
                            seed=2)
    finally:
        update_config(complex_pair="auto")


def test_forced_trip_after_a_thick_restart(clean_obs, monkeypatch):
    """``max_basis_size=24`` with blocks of 8: every cycle is a full-sweep
    block after the restart and a window block of 8 that the gate stops at
    its fourth step."""
    op, eng = _local(12)
    root, trips, forced = _check_forced_solve(
        monkeypatch, eng.matvec, 8, 16 + 3, n=op.basis.number_states, k=1,
        tol=1e-10, seed=4, max_basis_size=24, max_iters=400)
    # the first is the block before any restart, the others follow one
    assert len(forced) >= 2 and set(forced) == {(8, 16, 4)}
    assert root["omega_stops"] >= 2


def test_forced_trip_on_four_devices(clean_obs, monkeypatch):
    """``DistributedEngine`` on four virtual devices: the predicate comes
    from sharded reductions and every device leaves the loop together."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(12, 6)
    op.basis.build()
    eng = DistributedEngine(op, n_devices=4)
    _check_forced_solve(monkeypatch, eng.matvec, 16, 16 + 9,
                        v0=eng.random_hashed(seed=11), k=1, tol=1e-10)


def test_full_reorth_never_sees_the_tracker(clean_obs, monkeypatch):
    """``reorth="full"`` builds one program, no tracker and no ω rows."""
    def boom(*a, **k):
        raise AssertionError("reorth='full' touched the omega machinery")

    monkeypatch.setattr(L, "_OmegaTracker", boom)
    monkeypatch.setattr(L, "_omega_row", boom)
    monkeypatch.setattr(L, "_make_window_runner", boom)
    op, eng = _local(12)
    res = lanczos(eng.matvec, op.basis.number_states, k=1, tol=1e-10,
                  reorth="full")
    root = [e for e in obs.events("span") if e["name"] == "lanczos"][-1]
    assert res.converged and root["programs_built"] == 1
    assert root["steps_run"] == root["steps_counted"] == res.num_iters
    assert root["omega_stops"] == root["steps_discarded"] == 0
