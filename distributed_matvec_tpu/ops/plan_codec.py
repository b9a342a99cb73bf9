"""Compressed plan streams: host-side encode, on-device decode (format v1).

The streamed engine (``parallel/distributed.py``) turned the apply into a
bandwidth-bound stream of precomputed plan chunks, and the PR-7 roofline
names ``plan_h2d`` as the binding resource on symm configs — so the next
win must shrink the bytes themselves.  This module is the codec: plan
arrays are *encoded* once at build time and *decoded on device* inside the
chunk program, so the H2D stream (and the sidecar disk tier) carries the
encoded bytes while the arithmetic still runs on exact/f64-accumulated
values.

Because the plan is static, the codec can exploit structure the dynamic
fused path cannot:

* **Dead-entry compaction.**  Roughly half of a Heisenberg chunk's
  (row, term) entries are structurally dead (coefficient 0 — the term
  does not fire on that row).  The compressed tiers store only the live
  entries, each carrying an explicit bitpacked *row* index (the "gather"
  of the decode-gather kernel: ``x[row]`` replaces the implicit
  ``i // T``), shrinking the multiply + scatter work — not just the
  bytes — by the dead fraction (measured 48% on chain_24_symm).
* **Exchange-capacity trim.**  The build sizes the all_to_all buckets
  for the worst case (``Cap ≈ B·T/D × headroom``); the finished plan
  KNOWS the true maximum bucket fill.  The compressed tiers re-base the
  exchange slots to ``cap_eff = max fill`` (global across chunks/shards/
  ranks), halving the send buffer, the collective payload, and the
  receive-side ``segment_sum`` length on symm configs.  The remap is
  monotone per bucket and bucket-major order is preserved, so the
  accumulation ORDER — and therefore every bit of the result — is
  unchanged.

Per (row chunk, shard) the streamed plan holds four arrays
(``DistributedEngine._STREAM_ARRAYS``), encoded as:

``dest``  compressed tiers: TWO concatenated little-endian u32 word
    streams — the live entries' trimmed exchange slots at
    ``w_dest = bits(D·cap_eff)`` bits each (the ``D·cap_eff`` sentinel
    marks padding), then their row indices at ``w_row = bits(B−1)``
    bits.  Fixed-width bitpacking (the ISSUE's alternative to
    delta+varint): the decode is a branch-free vector gather+shift —
    one static program, no data-dependent loop.  ``off``: the raw
    [B·T] i32 array, unchanged.
``ridx``  [D·cap_eff] i32 (< M), bitpacked at ``bits(M−1)``; ``off``:
    raw i32.
``rok``   [D·cap_eff] bool, bitpacked 1 bit/flag — **in the
    uncompressed tier too** (a free lossless 8× on the flags,
    independent of the compress knob).
``coeff`` live entries only, **dictionary-coded** when the number of
    distinct coefficient values fits ``DICT_MAX`` (symm sectors:
    coefficients are ±W·n(β)/n(α)·χ over a finite set of orbit-norm
    ratios, so they repeat massively): u8/u16 codes on the wire + one
    tiny per-shard value table that is device-resident (uploaded once,
    NOT streamed).  Otherwise **raw** per the tier: ``lossless`` keeps
    f64 components, ``f32``/``bf16`` quantize (bf16 travels as its u16
    bit pattern — HDF5 has no bf16).  Decode always lands in f64 (c128)
    before the multiply, so accumulation stays f64 regardless of tier.

Tiers (``stream_compress`` knob / ``DMT_STREAM_COMPRESS``):

* ``off``       — today's raw layout with ``rok`` bitpacked.
  Bit-identical to fused (the existing gate).
* ``lossless``  — compaction + trim + exact f64/c128 coefficient
  values.  The decoded arithmetic is value-identical AND
  order-identical, so the apply stays bit-identical to fused — but the
  tier is gated by the *measured-error* gate, not asserted
  bit-identical (DESIGN.md §23).
* ``f32`` / ``bf16`` — coefficient values quantized; indices stay exact
  (they must).  Gated by measured relative error per config.

Versioned: ``spec["version"]`` rides the sidecar (and the engine
fingerprint), so a format change misses and rebuilds — never misreads.

The decode runs as plain XLA ops traced into the chunk program: XLA fuses
unpack+gather+multiply+segment-add into the one compiled chunk executable.
"""

from __future__ import annotations

import json
from typing import Callable, Dict, Optional

import numpy as np

__all__ = [
    "PLAN_CODEC_VERSION",
    "DICT_MAX",
    "TIERS",
    "bits_for",
    "packed_words",
    "pack_bits",
    "unpack_bits_np",
    "unpack_bits",
    "PlanCodec",
    "decode_plan_shard",
]

PLAN_CODEC_VERSION = 1

#: Per-shard dictionary ceiling: u16 codes.  Beyond it the coefficient
#: stream falls back to the tier's raw form.
DICT_MAX = 1 << 16

TIERS = ("off", "lossless", "f32", "bf16")


# ---------------------------------------------------------------------------
# fixed-width bitpacking (host pack / host + device unpack)


def bits_for(maxval: int) -> int:
    """Bits needed to represent values in ``[0, maxval]`` (min 1)."""
    return max(int(maxval).bit_length(), 1)


def packed_words(n: int, width: int) -> int:
    """u32 words holding ``n`` ``width``-bit values, +1 spare word so the
    branch-free two-word device read never runs off the end."""
    return (n * width + 31) // 32 + 1


#: pack_bits block size: bounds the transient bit-expansion scratch to
#: ~BLK·width bytes instead of O(n·width) — a chain_32-class dest stream
#: must not allocate a multi-hundred-MB intermediate during engine init.
#: A multiple of 8, so every block's bit run starts on a byte boundary.
_PACK_BLOCK = 1 << 17


def pack_bits(values, width: int) -> np.ndarray:
    """``values`` → little-endian u32 word stream at ``width`` bits each
    (bit ``k`` of value ``j`` lands at global bit ``j·width + k``).
    Packs in bounded blocks: peak scratch is O(_PACK_BLOCK·width), not
    O(n·width)."""
    if not 1 <= width <= 32:
        raise ValueError(f"width {width} outside [1, 32]")
    v = np.asarray(values).reshape(-1)
    if v.dtype == np.bool_:
        v = v.astype(np.uint8)
    v = v.astype(np.uint64)
    n = v.size
    if n and width < 64 and int(v.max()) >> width:
        raise ValueError(
            f"value {int(v.max())} does not fit in {width} bits")
    shifts = np.arange(width, dtype=np.uint64)
    nw = packed_words(n, width)
    out = np.zeros(nw * 4, np.uint8)
    for s in range(0, n, _PACK_BLOCK):
        blk = v[s: s + _PACK_BLOCK]
        bits = ((blk[:, None] >> shifts[None, :])
                & np.uint64(1)).astype(np.uint8)
        packed = np.packbits(bits.reshape(-1), bitorder="little")
        b0 = (s * width) // 8          # block-aligned: s·width ≡ 0 (mod 8)
        out[b0: b0 + packed.size] = packed
    return out.view("<u4").copy()


def unpack_bits_np(packed: np.ndarray, n: int, width: int) -> np.ndarray:
    """Host inverse of :func:`pack_bits` (u64 values) — the reference the
    device unpack is tested against, and the host round-trip decoder."""
    b = np.unpackbits(np.ascontiguousarray(packed).view(np.uint8),
                      bitorder="little")
    idx = (np.arange(n, dtype=np.int64)[:, None] * width
           + np.arange(width, dtype=np.int64)[None, :])
    sh = np.arange(width, dtype=np.uint64)[None, :]
    return (b[idx].astype(np.uint64) << sh).sum(axis=1, dtype=np.uint64)


def unpack_bits(packed, n: int, width: int):
    """Device (jax) unpack: one gather + shifts per value, branch-free
    (both words of a potentially-straddling value are always read; the
    second index is clamped so the read is in-bounds even without the
    spare word — a masked ``where`` discards it when unused).  Bit offsets
    are computed in i64: ``n·width`` routinely exceeds 2³² at
    chain_32-class shard sizes, and u32 offset wrap would decode silently
    wrong destinations."""
    import jax
    import jax.numpy as jnp

    bit0 = jax.lax.iota(jnp.int64, n) * width
    w0 = bit0 >> 5                       # i64 word index: no wrap anywhere
    off = (bit0 & 31).astype(jnp.uint32)
    lo = jnp.take(packed, w0) >> off
    spill = (off + jnp.uint32(width)) > jnp.uint32(32)
    # when spill is True, off >= 1, so the shift 32-off is in [1, 31];
    # the False branch's shift operand is forced to 0 (never 32 — XLA's
    # shift-by-bit-width is undefined)
    sh = jnp.where(spill, jnp.uint32(32) - off, jnp.uint32(0))
    w1 = jnp.minimum(w0 + 1, packed.shape[0] - 1)
    hi = jnp.where(spill, jnp.take(packed, w1) << sh, jnp.uint32(0))
    mask = jnp.uint32(0xFFFFFFFF) if width == 32 \
        else jnp.uint32((1 << width) - 1)
    return (lo | hi) & mask


# ---------------------------------------------------------------------------
# coefficient canonicalization / quantization


def _canonical(cf: np.ndarray, ckind: str) -> np.ndarray:
    """Flat complex128/float64 view of a coeff array (the dictionary's key
    space and the liveness test): pair [B, T, 2] folds to complex so one
    dict entry covers both components."""
    cf = np.asarray(cf)
    if ckind == "real":
        return cf.astype(np.float64, copy=False).reshape(-1)
    if ckind == "pair":
        return (cf[..., 0] + 1j * cf[..., 1]).reshape(-1)
    return cf.astype(np.complex128, copy=False).reshape(-1)


def _quantize(vals: np.ndarray, tier: str) -> np.ndarray:
    """Round values through the tier's storage precision (returned at full
    precision — the error is baked in exactly once, at encode time)."""
    if tier in ("off", "lossless"):
        return vals
    if np.iscomplexobj(vals):
        if tier == "f32":
            return vals.astype(np.complex64).astype(np.complex128)
        import ml_dtypes
        re = vals.real.astype(ml_dtypes.bfloat16).astype(np.float64)
        im = vals.imag.astype(ml_dtypes.bfloat16).astype(np.float64)
        return re + 1j * im
    if tier == "f32":
        return vals.astype(np.float32).astype(np.float64)
    import ml_dtypes
    return vals.astype(ml_dtypes.bfloat16).astype(np.float64)


def _raw_store(flat: np.ndarray, ckind: str, tier: str) -> np.ndarray:
    """Storage form of a compacted raw (non-dictionary) coefficient
    vector (canonical f64/c128 live values): [n] f64/f32/bf16-as-u16 for
    real, [n, 2] (re, im) columns for pair/complex."""
    if ckind != "real":
        flat = np.stack([flat.real, flat.imag], axis=-1)
    else:
        flat = flat.real
    if tier == "lossless":
        return flat.astype(np.float64)
    if tier == "f32":
        return flat.astype(np.float32)
    import ml_dtypes
    return flat.astype(ml_dtypes.bfloat16).view(np.uint16)


def _raw_load(stored: np.ndarray, ckind: str) -> np.ndarray:
    """Host inverse of :func:`_raw_store` back to canonical f64/c128."""
    if stored.dtype == np.uint16:
        import ml_dtypes
        v = stored.view(ml_dtypes.bfloat16).astype(np.float64)
    else:
        v = stored.astype(np.float64)
    if ckind != "real":
        return v[..., 0] + 1j * v[..., 1]
    return v


# ---------------------------------------------------------------------------
# the codec


class PlanCodec:
    """One engine's plan codec: a static ``spec`` (JSON-serializable —
    it rides the sidecar) plus the per-shard coefficient dictionaries.

    Construction paths: :meth:`build` scans the raw plan chunks once
    (fresh build), :meth:`from_spec_json` + :meth:`set_dict` restore from
    a sidecar.  Both yield byte-identical encodings for the same raw
    plan — the corrupt-chunk rebuild path re-encodes from structure and
    must reproduce the stored CRC.
    """

    def __init__(self, spec: Dict, dicts: Optional[Dict[int, np.ndarray]]
                 = None):
        if spec.get("version") != PLAN_CODEC_VERSION:
            raise ValueError(
                f"plan codec version {spec.get('version')} != "
                f"{PLAN_CODEC_VERSION}")
        if spec["tier"] not in TIERS:
            raise ValueError(f"unknown compress tier {spec['tier']!r}")
        self.spec = spec
        self.dicts: Dict[int, np.ndarray] = dicts or {}

    # -- construction ----------------------------------------------------

    @classmethod
    def build(cls, tier: str, chunks, n_dest: int, cap_build: int,
              n_devices: int, shard_size: int, cshape, ckind: str,
              agree: Optional[Callable] = None,
              dict_max: int = DICT_MAX,
              term_mask: Optional[np.ndarray] = None) -> "PlanCodec":
        """Codec for a freshly built plan.  ``chunks`` is the engine's
        ``[{shard: pc}]`` raw-chunk list; the scan measures the live-entry
        census (compaction bound), the true maximum bucket fill (capacity
        trim), and the distinct-coefficient census (dictionary decision).
        ``agree`` (multi-controller) maps the local decisions to job-wide
        ones — the encoded operand shapes enter a collective program, so
        every rank must encode identically.

        ``term_mask`` (hybrid mode, DESIGN.md §28) is a [T] bool array
        marking which terms' entries are STORED (True = streamed); the
        other terms are recomputed on device per apply.  The capacity trim
        still measures ALL live entries — the merged slot layout is the
        full plan's, so the streamed entries' stored slots stay exactly
        the slots the full-streamed apply would use and the recompute side
        fills the per-bucket complement — while the dest/row/coeff streams
        (and the dictionary) carry only the masked subset."""
        D = int(n_devices)
        T = int(cshape[1])
        spec = {"version": PLAN_CODEC_VERSION, "tier": tier,
                "n_dest": int(n_dest), "D": D,
                "cap_build": int(cap_build), "cap_eff": int(cap_build),
                "n_recv": D * int(cap_build),
                "w_dest": bits_for(D * int(cap_build)),
                "w_ridx": bits_for(max(shard_size - 1, 1)),
                "w_row": bits_for(max(int(cshape[0]) - 1, 1)),
                "n_live": int(n_dest),
                "cshape": [int(s) for s in cshape], "ckind": ckind,
                "coeff": "raw", "code_bits": 0, "ndict": 0}
        if term_mask is not None:
            term_mask = np.asarray(term_mask, bool).reshape(-1)
            if term_mask.size != T:
                raise ValueError(
                    f"term_mask has {term_mask.size} entries for "
                    f"{T} terms")
            spec["hybrid"] = True
            spec["stream_terms"] = [int(t) for t in
                                    np.nonzero(term_mask)[0]]
            if tier == "off":
                raise ValueError(
                    "a term-masked (hybrid) plan requires a compacted "
                    "tier — the raw [B, T] layout cannot drop terms")
        if tier == "off":
            return cls(spec)
        mask_flat = None if term_mask is None \
            else np.tile(term_mask, int(cshape[0]))
        uniq: Dict[int, np.ndarray] = {}
        n_live = 0
        fill = 0
        for per in chunks:
            for d, pc in per.items():
                flat = _canonical(pc["coeff"], ckind)
                # live = contributes to the apply: nonzero coefficient AND
                # a real exchange slot (the D·Cap sentinel marks entries
                # the raw scatter drops — dead rows, and overflow, which
                # the build already validated to zero)
                dest_all = np.asarray(pc["dest"], np.int64).reshape(-1)
                live = (flat != 0) & (dest_all < D * cap_build)
                dest = dest_all[live]
                if dest.size:
                    # in-bucket rank: dead entries sit in their own
                    # bucket (the D·Cap sentinel), so live positions are
                    # consecutive per bucket and max(pos)+1 is the fill.
                    # ALL live entries count here even under a term mask:
                    # the trim defines the merged slot space
                    fill = max(fill, int((dest % cap_build).max()) + 1)
                if mask_flat is not None:
                    live &= mask_flat
                n_live = max(n_live, int(live.sum()))
                u = np.unique(flat[live])
                prev = uniq.get(d)
                uniq[d] = u if prev is None else \
                    np.unique(np.concatenate([prev, u]))
        nd = max((u.size for u in uniq.values()), default=0)
        use_dict = bool(uniq) and nd <= dict_max
        fill = max(fill, 1)
        n_live = max(((n_live + 7) // 8) * 8, 8)
        if agree is not None:
            use_dict, nd, fill, n_live = agree(use_dict, nd, fill, n_live)
        spec["cap_eff"] = int(min(fill, cap_build))
        spec["n_recv"] = D * spec["cap_eff"]
        spec["w_dest"] = bits_for(spec["n_recv"])
        spec["n_live"] = int(min(n_live, n_dest))
        if use_dict and nd:
            spec["coeff"] = "dict"
            spec["code_bits"] = 8 if nd <= (1 << 8) else 16
            spec["ndict"] = int(nd)
            return cls(spec, uniq)
        return cls(spec)

    def spec_json(self) -> str:
        return json.dumps(self.spec, sort_keys=True)

    @classmethod
    def from_spec_json(cls, s: str) -> "PlanCodec":
        spec = json.loads(s)
        for k in ("tier", "n_dest", "D", "cap_build", "cap_eff", "n_recv",
                  "w_dest", "w_ridx", "w_row", "n_live", "cshape", "ckind",
                  "coeff"):
            if k not in spec:
                raise ValueError(f"codec spec missing {k!r}")
        return cls(spec)

    def set_dict(self, d: int, values: np.ndarray) -> None:
        """Attach shard ``d``'s dictionary (sidecar restore path).  Stored
        values are the original-precision sorted table :meth:`dict_store`
        wrote — real f64 or (re, im) f64 pairs."""
        if self.spec["ckind"] == "real":
            self.dicts[d] = np.asarray(values, np.float64).reshape(-1)
        else:
            v = np.asarray(values, np.float64)
            self.dicts[d] = v[:, 0] + 1j * v[:, 1]

    def dict_store(self, d: int) -> np.ndarray:
        """Shard ``d``'s dictionary in sidecar form: the UNPADDED sorted
        original-precision values (always plain f64 columns —
        HDF5-friendly, negligible next to the chunk stream).  Originals,
        not quantized: they are the ``searchsorted`` key space, and the
        corrupt-chunk rebuild path re-encodes raw coefficients against a
        restored codec — quantized keys would never match.  Quantization
        is applied downstream, in :meth:`dict_device_row` and
        :meth:`decode_chunk_host`."""
        vals = self.dicts[d]
        if self.spec["ckind"] == "real":
            return np.asarray(vals.real, np.float64)
        return np.stack([vals.real, vals.imag], axis=-1).astype(np.float64)

    def dict_device_row(self, d: int) -> np.ndarray:
        """Shard ``d``'s device-resident decode table, padded to the
        agreed ``ndict`` so the assembled [D, nd] operand is uniform:
        [nd] f64 (real), [nd, 2] f64 (pair), or [nd] c128 (complex) —
        what the in-program code gather indexes.  Values are quantized
        per the tier (the one place the precision loss happens).  Empty
        row when the codec carries no dict."""
        ckind = self.spec["ckind"]
        nd = self.spec["ndict"]
        if not nd or self.spec["coeff"] != "dict":
            if ckind == "complex":
                return np.zeros(0, np.complex128)
            return np.zeros((0, 2) if ckind == "pair" else 0, np.float64)
        vals = _quantize(self.dicts[d], self.spec["tier"])
        if ckind == "real":
            out = np.zeros(nd, np.float64)
            out[: vals.size] = vals.real
            return out
        if ckind == "pair":
            out = np.zeros((nd, 2), np.float64)
            out[: vals.size, 0] = vals.real
            out[: vals.size, 1] = vals.imag
            return out
        out = np.zeros(nd, np.complex128)
        out[: vals.size] = vals
        return out

    # -- compaction (host) ------------------------------------------------

    def term_mask(self) -> Optional[np.ndarray]:
        """The [T] bool stream mask of a hybrid (term-masked) codec, None
        otherwise — reconstructed from the spec so a sidecar restore
        carries the split without a separate payload field."""
        if not self.spec.get("hybrid"):
            return None
        mask = np.zeros(int(self.spec["cshape"][1]), bool)
        mask[np.asarray(self.spec.get("stream_terms", []), np.int64)] = True
        return mask

    def compact_raw(self, pc: Dict) -> Dict:
        """One raw (chunk, shard) record → its compacted host-side form:
        live entries only (the masked term subset for a hybrid codec),
        trimmed exchange slots, explicit row indices.
        The shared oracle of :meth:`encode_chunk` and the round-trip
        tests.  Keys: ``dest``/``row``/``coeff`` ([n_live], canonical
        f64/c128 coeff, pads: drop-sentinel / 0 / 0) and
        ``ridx``/``rok`` ([D·cap_eff], the per-bucket prefix of the raw
        receive layout)."""
        s = self.spec
        D, cap_b, cap_e = s["D"], s["cap_build"], s["cap_eff"]
        nl = s["n_live"]
        flat = _canonical(pc["coeff"], s["ckind"])
        dest_all = np.asarray(pc["dest"], np.int64).reshape(-1)
        live = (flat != 0) & (dest_all < D * cap_b)   # build's definition
        mask = self.term_mask()
        if mask is not None:
            live &= np.tile(mask, int(s["cshape"][0]))
        dest = dest_all[live]
        if dest.size > nl:
            raise ValueError(
                f"{dest.size} live entries exceed the codec's n_live "
                f"{nl} — plan/codec mismatch")
        key = dest // cap_b
        pos = dest - key * cap_b
        if pos.size and int(pos.max()) >= cap_e:
            raise ValueError(
                f"bucket fill {int(pos.max()) + 1} exceeds the codec's "
                f"cap_eff {cap_e} — plan/codec mismatch")
        d_out = np.full(nl, D * cap_e, np.int64)
        d_out[: dest.size] = key * cap_e + pos
        r_out = np.zeros(nl, np.int64)
        r_out[: dest.size] = np.nonzero(live)[0] // s["cshape"][1]
        c_out = np.zeros(nl, flat.dtype)
        c_out[: dest.size] = flat[live]
        ridx = np.asarray(pc["ridx"]).reshape(D, cap_b)[:, :cap_e]
        rok = np.asarray(pc["rok"]).reshape(D, cap_b)[:, :cap_e]
        return {"dest": d_out, "row": r_out, "coeff": c_out,
                "ridx": np.ascontiguousarray(ridx).reshape(-1),
                "rok": np.ascontiguousarray(rok).reshape(-1)}

    # -- encode / decode (host) ------------------------------------------

    def encode_chunk(self, pc: Dict, d: int) -> Dict:
        """One raw (chunk, shard) record → its encoded form (same keys, so
        the CRC/sidecar/upload machinery is tier-blind).  Compressed
        tiers fold the row-index stream into the ``dest`` array (two
        concatenated word streams) — no schema change."""
        s = self.spec
        if s["tier"] == "off":
            return {"dest": np.asarray(pc["dest"]),
                    "coeff": np.asarray(pc["coeff"]),
                    "ridx": np.asarray(pc["ridx"]),
                    "rok": pack_bits(pc["rok"], 1)}
        cp = self.compact_raw(pc)
        out = {"dest": np.concatenate([pack_bits(cp["dest"], s["w_dest"]),
                                       pack_bits(cp["row"], s["w_row"])]),
               "ridx": pack_bits(cp["ridx"], s["w_ridx"]),
               "rok": pack_bits(cp["rok"], 1)}
        if s["coeff"] == "dict":
            codes = np.searchsorted(self.dicts[d], cp["coeff"])
            np.clip(codes, 0, max(self.dicts[d].size - 1, 0), out=codes)
            ok = self.dicts[d][codes] == cp["coeff"]
            # padding zeros may legitimately be absent from the dict —
            # their decode value is irrelevant (drop-sentinel dest)
            if not np.all(ok | (cp["coeff"] == 0)):
                raise ValueError(
                    f"shard {d}: coefficient outside its dictionary — "
                    "plan/codec mismatch (stale codec for a rebuilt "
                    "plan?)")
            # pads (coeff 0) take a deterministic in-range code: their
            # decode value is dropped at the sentinel dest either way
            pad_code = min(int(np.searchsorted(self.dicts[d], 0.0)),
                           max(self.dicts[d].size - 1, 0))
            codes[cp["coeff"] == 0] = pad_code
            out["coeff"] = codes.astype(
                np.uint8 if s["code_bits"] == 8 else np.uint16)
        else:
            out["coeff"] = _raw_store(cp["coeff"], s["ckind"], s["tier"])
        return out

    def decode_chunk_host(self, enc: Dict, d: int) -> Dict:
        """Host inverse of :meth:`encode_chunk` — the round-trip test
        oracle and the shape/dtype reference for the device decode.  For
        the ``off`` tier this is the raw record back; compressed tiers
        return the COMPACT form (:meth:`compact_raw` keys — the raw
        (row, term) grid is not invertible once dead entries are gone,
        and the device consumes the compact form anyway).  Quantized
        tiers return the quantized values at full precision."""
        s = self.spec
        n_recv = s["n_recv"]
        if s["tier"] == "off":
            return {"dest": enc["dest"], "coeff": enc["coeff"],
                    "ridx": enc["ridx"],
                    "rok": unpack_bits_np(enc["rok"], n_recv,
                                          1).astype(bool)}
        nl = s["n_live"]
        nwd = packed_words(nl, s["w_dest"])
        dest = unpack_bits_np(enc["dest"][:nwd], nl,
                              s["w_dest"]).astype(np.int64)
        row = unpack_bits_np(enc["dest"][nwd:], nl,
                             s["w_row"]).astype(np.int64)
        ridx = unpack_bits_np(enc["ridx"], n_recv,
                              s["w_ridx"]).astype(np.int32)
        rok = unpack_bits_np(enc["rok"], n_recv, 1).astype(bool)
        if s["coeff"] == "dict":
            coeff = _quantize(self.dicts[d], s["tier"])[
                np.asarray(enc["coeff"], np.int64)]
        else:
            coeff = _raw_load(np.asarray(enc["coeff"]), s["ckind"])
        if s["ckind"] == "real":
            coeff = coeff.real if np.iscomplexobj(coeff) else coeff
        # padding entries decode to dest == drop sentinel; zero their
        # coeff so the host form equals compact_raw exactly
        coeff = np.where(dest == n_recv, 0, coeff)
        return {"dest": dest, "row": row, "coeff": coeff,
                "ridx": ridx, "rok": rok}

    # -- size accounting --------------------------------------------------

    def raw_chunk_bytes(self) -> int:
        """Uncompressed bytes of ONE (chunk, shard) record — dest i32 +
        native-dtype coeff + untrimmed ridx i32 + rok byte-bool.  The
        denominator of the compression ratio (and ``plan_bytes_raw``),
        identical whether the plan was freshly built or
        sidecar-restored."""
        s = self.spec
        cb = 8 if s["ckind"] == "real" else 16
        ncf = int(np.prod(s["cshape"][:2]))
        n_recv_raw = s["D"] * s["cap_build"]
        return s["n_dest"] * 4 + ncf * cb + n_recv_raw * (4 + 1)

    @staticmethod
    def encoded_bytes(enc: Dict) -> int:
        return sum(int(np.asarray(a).nbytes) for a in enc.values())


# ---------------------------------------------------------------------------
# device decode (traced into the streamed chunk program)


def decode_plan_shard(spec: Dict, dest, coeff, ridx, rok, cdict):
    """Shard-local device decode.  ``off`` tier: pass-through plus the
    rok mask unpack, returning ``(dest, coeff, ridx, rok)`` in the raw
    chunk-program layout.  Compressed tiers: the compact form
    ``(dest i32 [n_live], row i32 [n_live], coeff f64/c128/[.,2]f64,
    ridx i32 [D·cap_eff], rok bool)``.  Pure jax ops — traced into the
    (shard_mapped) chunk program, where XLA fuses the unpack/gather
    chain with the multiply + scatter + ``segment_sum`` that follows
    (the "fused decode" path)."""
    import jax.numpy as jnp

    n_recv = spec["n_recv"]
    rok_b = unpack_bits(rok, n_recv, 1).astype(bool)
    if spec["tier"] == "off":
        return dest, coeff, ridx, rok_b
    nl = spec["n_live"]
    nwd = packed_words(nl, spec["w_dest"])
    dest_i = unpack_bits(dest[:nwd], nl, spec["w_dest"]).astype(jnp.int32)
    row_i = unpack_bits(dest[nwd:], nl, spec["w_row"]).astype(jnp.int32)
    ridx_i = unpack_bits(ridx, n_recv, spec["w_ridx"]).astype(jnp.int32)
    cf = _decode_coeff_vals(spec, coeff, cdict)
    return dest_i, row_i, cf, ridx_i, rok_b


def _decode_coeff_vals(spec: Dict, coeff, cdict):
    """Compacted coefficient stream → live values at full precision:
    [n_live] f64 (real), [n_live, 2] f64 (pair), [n_live] c128
    (complex)."""
    import jax
    import jax.numpy as jnp

    ckind = spec["ckind"]
    if spec["coeff"] == "dict":
        return cdict[coeff.astype(jnp.int32)]
    if coeff.dtype == jnp.uint16:             # bf16 raw, as bit patterns
        v = jax.lax.bitcast_convert_type(
            coeff, jnp.bfloat16).astype(jnp.float64)
    else:
        v = coeff.astype(jnp.float64)
    if ckind == "complex":
        return (v[..., 0] + 1j * v[..., 1]).astype(jnp.complex128)
    return v
