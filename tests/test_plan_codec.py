"""Compressed plan streams (ops/plan_codec.py + the streamed engine tiers).

Codec invariants: the bitpack round-trips exactly on host and device; the
lossless tier decodes to the raw plan bit-for-bit (so the apply stays
bit-identical to fused); the quantized tiers stay inside their documented
bounds with f64 accumulation; the sidecar carries the codec (v3
fingerprint — older-format files miss and rebuild, never misread); and a
corrupt compressed chunk heals through the PR 6 ``plan_chunk_rebuilt``
path bit-consistently.
"""

import os

import jax
import numpy as np
import pytest

from distributed_matvec_tpu.ops import plan_codec as PC
from distributed_matvec_tpu.parallel.distributed import DistributedEngine
from distributed_matvec_tpu.utils.config import update_config

from test_operator import build_heisenberg


def _ndev() -> int:
    return len(jax.devices())


needs_4 = pytest.mark.skipif("_ndev() < 4", reason="needs 4 virtual devices")


@pytest.fixture
def tier(request):
    """Set a stream_compress tier for one test, restoring off after."""
    update_config(stream_compress=request.param)
    yield request.param
    update_config(stream_compress="off")


# -- bitpacking -------------------------------------------------------------


@pytest.mark.parametrize("width", [1, 3, 8, 13, 17, 24, 31, 32])
def test_pack_bits_roundtrip(width, rng):
    n = 517
    v = rng.integers(0, (1 << width) - 1, n, endpoint=True,
                     dtype=np.uint64)
    packed = PC.pack_bits(v, width)
    assert packed.dtype == np.uint32
    assert packed.size == PC.packed_words(n, width)
    assert np.array_equal(PC.unpack_bits_np(packed, n, width), v)
    # device unpack agrees with the host reference
    import jax.numpy as jnp
    dev = jax.jit(lambda p: PC.unpack_bits(p, n, width))(jnp.asarray(packed))
    assert np.array_equal(np.asarray(dev).astype(np.uint64), v)


def test_pack_bits_rejects_overflow():
    with pytest.raises(ValueError, match="does not fit"):
        PC.pack_bits(np.array([9], np.uint64), 3)


# -- chunk round trip -------------------------------------------------------


def _chunk(rng, B=24, T=5, n_recv=64, M=48, ckind="real", values=None):
    if values is None:
        values = np.array([0.0, 0.5, -0.5, 1.25, -2.0])
    cf = rng.choice(values, (B, T))
    if ckind == "pair":
        cf = np.stack([cf, rng.choice(values, (B, T))], axis=-1)
    elif ckind == "complex":
        cf = cf + 1j * rng.choice(values, (B, T))
    return {"dest": rng.integers(0, n_recv, B * T,
                                 endpoint=True).astype(np.int32),
            "coeff": cf,
            "ridx": rng.integers(0, M, n_recv).astype(np.int32),
            "rok": rng.integers(0, 2, n_recv).astype(bool)}


@pytest.mark.parametrize("ckind", ["real", "pair", "complex"])
@pytest.mark.parametrize("tier_name", ["off", "lossless", "f32", "bf16"])
def test_codec_chunk_roundtrip(tier_name, ckind, rng):
    B, T, n_recv, M = 24, 5, 64, 48
    cshape = (B, T) + ((2,) if ckind == "pair" else ())
    pc = _chunk(rng, B, T, n_recv, M, ckind)
    codec = PC.PlanCodec.build(tier_name, [{0: pc}], n_dest=B * T,
                               cap_build=n_recv, n_devices=1,
                               shard_size=M, cshape=cshape, ckind=ckind)
    enc = codec.encode_chunk(pc, 0)
    dec = codec.decode_chunk_host(enc, 0)
    if tier_name == "off":
        for k in ("dest", "ridx", "rok"):
            assert np.array_equal(np.asarray(dec[k]), np.asarray(pc[k])), k
        assert np.array_equal(np.asarray(dec["coeff"]), pc["coeff"])
        return
    # compressed tiers round-trip the COMPACT form (live entries +
    # trimmed receive layout); compact_raw is the oracle
    ref = codec.compact_raw(pc)
    for k in ("dest", "row", "ridx", "rok"):
        assert np.array_equal(np.asarray(dec[k]), np.asarray(ref[k])), k
    if tier_name == "lossless":
        assert np.array_equal(np.asarray(dec["coeff"]), ref["coeff"])
    else:
        rtol = 1e-6 if tier_name == "f32" else 1e-2
        np.testing.assert_allclose(dec["coeff"], ref["coeff"], rtol=rtol,
                                   atol=rtol)
    assert codec.spec["coeff"] == "dict"
    assert PC.PlanCodec.encoded_bytes(enc) * 2 < codec.raw_chunk_bytes()


def test_codec_raw_fallback_when_dict_overflows(rng):
    """Continuous coefficients blow the dictionary: the codec degrades to
    raw (quantized) compacted coefficient vectors, still with packed
    indices."""
    B, T, n_recv, M = 16, 4, 32, 32
    pc = _chunk(rng, B, T, n_recv, M, values=rng.standard_normal(B * T))
    codec = PC.PlanCodec.build("f32", [{0: pc}], n_dest=B * T,
                               cap_build=n_recv, n_devices=1,
                               shard_size=M, cshape=(B, T),
                               ckind="real", dict_max=8)
    assert codec.spec["coeff"] == "raw"
    enc = codec.encode_chunk(pc, 0)
    assert enc["coeff"].dtype == np.float32
    dec = codec.decode_chunk_host(enc, 0)
    ref = codec.compact_raw(pc)
    np.testing.assert_allclose(dec["coeff"], ref["coeff"], rtol=1e-6)
    assert np.array_equal(dec["dest"], ref["dest"])
    assert np.array_equal(dec["row"], ref["row"])


def test_codec_compaction_and_trim(rng):
    """The compressed spec reflects the measured plan: n_live covers the
    live census (padded to 8), cap_eff equals the max bucket fill, and
    the compact form's row/dest agree with a hand computation."""
    B, T, cap, M = 16, 4, 40, 32
    pc = _chunk(rng, B, T, cap, M)
    codec = PC.PlanCodec.build("lossless", [{0: pc}], n_dest=B * T,
                               cap_build=cap, n_devices=1,
                               shard_size=M, cshape=(B, T), ckind="real")
    dest_all = np.asarray(pc["dest"], np.int64)
    live = (pc["coeff"].reshape(-1) != 0) & (dest_all < cap)
    n_live = int(live.sum())
    assert n_live <= codec.spec["n_live"] <= n_live + 8
    assert codec.spec["cap_eff"] == max(
        int((dest_all[live] % cap).max()) + 1, 1)
    cp = codec.compact_raw(pc)
    rows = np.nonzero(live)[0] // T
    assert np.array_equal(cp["row"][:n_live], rows)
    assert np.all(cp["dest"][n_live:] == codec.spec["n_recv"])
    assert cp["ridx"].size == codec.spec["n_recv"]


def test_codec_spec_json_roundtrip(rng):
    pc = _chunk(rng)
    codec = PC.PlanCodec.build("lossless", [{0: pc}], n_dest=120,
                               cap_build=64, n_devices=1, shard_size=48,
                               cshape=(24, 5), ckind="real")
    restored = PC.PlanCodec.from_spec_json(codec.spec_json())
    assert restored.spec == codec.spec
    restored.set_dict(0, codec.dict_store(0))
    assert np.array_equal(restored.dicts[0], codec.dicts[0])
    # a restored codec re-encodes BIT-identically (the corrupt-chunk
    # rebuild contract: the healed chunk must match the stored CRC)
    e1, e2 = codec.encode_chunk(pc, 0), restored.encode_chunk(pc, 0)
    for k in e1:
        assert np.array_equal(e1[k], e2[k]), k


def test_codec_version_gate():
    with pytest.raises(ValueError, match="version"):
        PC.PlanCodec({"version": 99, "tier": "off"})


# -- engine tiers vs the fused truth ---------------------------------------


@needs_4
@pytest.mark.parametrize("tier", ["off", "lossless"], indirect=True)
def test_compressed_stream_bit_identical_to_fused(tier, rng):
    """off and lossless tiers reproduce fused to the BIT (single + k=3
    batch) on a |G|>1 symm config — lossless decodes exact f64 dictionary
    values, so nothing changes numerically."""
    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    ef = DistributedEngine(op, n_devices=4, mode="fused", batch_size=64)
    es = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=64)
    yf = np.asarray(ef.matvec(ef.to_hashed(x)))
    ys = np.asarray(es.matvec(es.to_hashed(x)))
    np.testing.assert_array_equal(yf, ys)
    X3 = np.stack([x, -x, 0.5 * x], axis=1)
    np.testing.assert_array_equal(
        np.asarray(ef.matvec(ef.to_hashed(X3))),
        np.asarray(es.matvec(es.to_hashed(X3))))
    if tier == "lossless":
        assert es._codec.spec["coeff"] == "dict"
        assert es.plan_bytes * 2 < es.plan_bytes_raw
    else:
        # the satellite: rok is bitpacked even uncompressed
        assert es._plan_chunks[0][0]["rok"].dtype == np.uint32


@needs_4
@pytest.mark.parametrize("tier", ["f32", "bf16"], indirect=True)
def test_quantized_tiers_within_documented_bounds(tier, rng):
    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    ef = DistributedEngine(op, n_devices=4, mode="fused", batch_size=64)
    es = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=64)
    yf = np.asarray(ef.matvec(ef.to_hashed(x)))
    ys = np.asarray(es.matvec(es.to_hashed(x)))
    rel = np.max(np.abs(ys - yf)) / np.max(np.abs(yf))
    assert rel <= (1e-6 if tier == "f32" else 1e-2), (tier, rel)


@needs_4
@pytest.mark.parametrize("tier", ["lossless"], indirect=True)
def test_compressed_complex_sector(tier, rng):
    """Native-c128 momentum sector: complex dictionary, exact decode."""
    op = build_heisenberg(10, 5, None, [([*range(1, 10), 0], 1)])
    op.basis.build()
    x = (rng.random(op.basis.number_states) - 0.5).astype(np.complex128)
    ef = DistributedEngine(op, n_devices=4, mode="fused", batch_size=64)
    es = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=64)
    np.testing.assert_array_equal(
        np.asarray(ef.matvec(ef.to_hashed(x))),
        np.asarray(es.matvec(es.to_hashed(x))))


# -- sidecar: v3 fingerprint, compressed round trip, corrupt chunk ---------


@needs_4
@pytest.mark.parametrize("tier", ["lossless"], indirect=True)
def test_compressed_sidecar_roundtrip_and_disk_tier(tier, tmp_path, rng,
                                                    monkeypatch):
    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    e1 = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=64)
    assert not e1.structure_restored
    y1 = np.asarray(e1.matvec(e1.to_hashed(x)))
    e2 = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=64)
    assert e2.structure_restored
    assert e2._codec.spec == e1._codec.spec
    np.testing.assert_array_equal(
        y1, np.asarray(e2.matvec(e2.to_hashed(x))))
    # disk tier reads the ENCODED chunks back per apply
    update_config(stream_plan_ram_gb=0.0)
    try:
        e3 = DistributedEngine(op, n_devices=4, mode="streamed",
                               batch_size=64)
        assert e3.structure_restored
        assert e3._plan_chunks is None and e3._plan_disk
        np.testing.assert_array_equal(
            y1, np.asarray(e3.matvec(e3.to_hashed(x))))
    finally:
        update_config(stream_plan_ram_gb=8.0)


@needs_4
def test_sidecar_fingerprint_tier_and_format_miss(tmp_path, rng,
                                                  monkeypatch):
    """The v3 fingerprint bakes in the compress tier and codec version:
    an off-tier sidecar never restores into a lossless engine (and vice
    versa), and a v2-era fingerprint (no codec tag) cannot match — the
    miss-and-rebuild path, never a misread."""
    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    e_off = DistributedEngine(op, n_devices=4, mode="streamed",
                              batch_size=64)
    assert not e_off.structure_restored
    fp_off = e_off._structure_fingerprint()
    update_config(stream_compress="lossless")
    try:
        e_l = DistributedEngine(op, n_devices=4, mode="streamed",
                                batch_size=64)
        # the off-tier sidecar exists but must MISS for the lossless tier
        assert not e_l.structure_restored
        assert e_l._structure_fingerprint() != fp_off
        np.testing.assert_array_equal(
            np.asarray(e_off.matvec(e_off.to_hashed(x))),
            np.asarray(e_l.matvec(e_l.to_hashed(x))))
        # and a second lossless engine restores its own sidecar
        e_l2 = DistributedEngine(op, n_devices=4, mode="streamed",
                                 batch_size=64)
        assert e_l2.structure_restored
    finally:
        update_config(stream_compress="off")
    # a sidecar whose fingerprint predates v3 (simulated stale write at
    # the SAME path) is ignored: the engine rebuilds instead of reading
    # the old format
    import glob

    import h5py
    side = glob.glob(str(tmp_path / "art" / "structure" / "**"
                         / "*.stream.h5"), recursive=True)
    assert side
    for s in side:
        with h5py.File(s, "r+") as f:
            f["engine_structure"].attrs["fingerprint"] = "v2-era-stale"
    e_new = DistributedEngine(op, n_devices=4, mode="streamed",
                              batch_size=64)
    assert not e_new.structure_restored
    np.testing.assert_array_equal(
        np.asarray(e_off.matvec(e_off.to_hashed(x))),
        np.asarray(e_new.matvec(e_new.to_hashed(x))))


@needs_4
@pytest.mark.parametrize("tier", ["lossless"], indirect=True)
def test_corrupt_compressed_chunk_rebuilds_bit_consistently(
        tier, tmp_path, rng, monkeypatch):
    """A checksum-corrupt ENCODED chunk on the disk tier heals through the
    PR 6 ``plan_chunk_rebuilt`` path: the chunk re-resolves from structure,
    re-encodes with the restored codec, and the apply stays bit-identical
    to the uncorrupted plan."""
    import gc
    import glob

    import h5py

    from distributed_matvec_tpu import obs

    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    e1 = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=64)
    y1 = np.asarray(e1.matvec(e1.to_hashed(x)))
    del e1
    gc.collect()          # close any lazily-opened sidecar handles
    side = glob.glob(str(tmp_path / "art" / "structure" / "**"
                         / "*.stream.h5"), recursive=True)
    assert side
    with h5py.File(side[0], "r+") as f:
        g = f["engine_structure"]
        key = sorted(k for k in g if k.startswith("coeff_"))[0]
        a = g[key][...]
        flat = a.reshape(-1)
        flat[0] ^= np.asarray(1, a.dtype)     # encoded arrays are integral
        del g[key]
        g.create_dataset(key, data=a)
    update_config(stream_plan_ram_gb=0.0)
    obs.reset_all()
    try:
        e2 = DistributedEngine(op, n_devices=4, mode="streamed",
                               batch_size=64)
        assert e2.structure_restored and e2._plan_disk
        y2 = np.asarray(e2.matvec(e2.to_hashed(x)))
        np.testing.assert_array_equal(y1, y2)
        assert obs.events("plan_chunk_rebuilt"), \
            "corrupt chunk healed without the rebuild path"
    finally:
        update_config(stream_plan_ram_gb=8.0)
        obs.reset_all()


# -- observability / planner plumbing --------------------------------------


@needs_4
@pytest.mark.parametrize("tier", ["lossless"], indirect=True)
def test_phase_bytes_and_ledger_report_encoded(tier, rng):
    """The measurement plane reports ENCODED bytes end to end: the
    apply_phases plan_h2d bytes, the bytes_h2d counter, the plan_stream
    event, and the memory-ledger context the capacity planner reads."""
    from distributed_matvec_tpu import obs

    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    obs.reset_all()
    try:
        es = DistributedEngine(op, n_devices=4, mode="streamed",
                               batch_size=64)
        assert es.plan_bytes < es.plan_bytes_raw
        ps = obs.events("plan_stream")[-1]
        assert ps["plan_bytes"] == es.plan_bytes
        assert ps["plan_bytes_raw"] == es.plan_bytes_raw
        assert ps["compress"] == "lossless"
        assert ps["compress_ratio"] == pytest.approx(
            es.plan_bytes_raw / es.plan_bytes, rel=1e-3)
        led = [e for e in obs.events("memory_ledger")
               if e.get("mode") == "streamed"][-1]
        assert led["plan_bytes"] == es.plan_bytes
        assert led["plan_bytes_raw"] == es.plan_bytes_raw
        assert led["stream_compress"] == "lossless"
        c0 = obs.snapshot()["counters"].get(
            "bytes_h2d{path=plan_stream}", 0)
        x = rng.random(op.basis.number_states) - 0.5
        es.matvec(es.to_hashed(x))
        c1 = obs.snapshot()["counters"]["bytes_h2d{path=plan_stream}"]
        assert c1 - c0 == es.plan_bytes     # the stream carries encoded
        pev = [e for e in obs.events("apply_phases")
               if e.get("mode") == "streamed"][-1]
        assert pev["phases"]["plan_h2d"]["bytes"] == es.plan_bytes
    finally:
        obs.reset_all()


def test_capacity_models_compressed_settings():
    import importlib.util
    import os as _os
    spec = importlib.util.spec_from_file_location(
        "capacity", _os.path.join(_os.path.dirname(__file__), "..",
                                  "tools", "capacity.py"))
    cap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cap)
    off = cap.stream_plan_bytes_per_row(36, False, "off")
    loss = cap.stream_plan_bytes_per_row(36, False, "lossless")
    f32 = cap.stream_plan_bytes_per_row(36, False, "f32")
    bf16 = cap.stream_plan_bytes_per_row(36, False, "bf16")
    assert off > f32 > loss and off > bf16
    assert off / loss >= 2.0
    rep = cap.plan(63_000_000, 36, 24, False, 16.0, 8, 3, 1,
                   stream_compress="lossless")
    m = rep["modes"]["streamed"]
    assert m["stream_compress"] == "lossless"
    by = m["host_plan_bytes_per_row_by_compress"]
    assert set(by) == {"off", "lossless", "f32", "bf16"}
    assert m["host_plan_bytes_per_row"] == by["lossless"]
    # measured calibration anchors the recorded tier and scales the rest
    measured = {"mode": "streamed", "n_padded": 1000, "plan_bytes": 100_000,
                "plan_bytes_raw": 420_000, "stream_compress": "lossless"}
    rep2 = cap.plan(63_000_000, 36, 24, False, 16.0, 8, 3, 1,
                    measured=measured, stream_compress="lossless")
    by2 = rep2["modes"]["streamed"]["host_plan_bytes_per_row_by_compress"]
    assert by2["lossless"] == pytest.approx(100.0)
    assert by2["off"] == pytest.approx(420.0)
