"""Quantized collectives for data-parallel gradient averaging.

The paper's Fig. 5 compresses *model gradients* on the DP axis
(QuantizedAdam).  Two wire forms carry the same math:

* ``ef_psum_mean_bucket`` — the conservative psum wire:

      s      = pmax(rowwise absmax)          (tiny, fp32)
      codes  = encode_codes_with_scale(x, s) (int32 accumulator form)
      sum    = psum(int32 codes)             (HLO: i32 lanes)
      mean   = decode_sum_mean(sum, s, n)

* ``ring_ef_reduce_mean_bucket`` — the bandwidth-optimal ring: the SAME
  encode additionally emits the packed b-bit payload (one fused pass),
  and the collective ships that payload itself.  Reduce-scatter half:
  the bucket is cut into N row segments; at step t every device
  ``ppermute``s its own packed codes of segment (i+t) mod N straight to
  that segment's owner (a rotation-by-t permutation — N-1 steps, one
  packed segment per device per step, exactly ``Q.wire_bytes`` of
  payload per hop), and the owner folds the unpack into a fused
  int32 unpack-accumulate (`B.accumulate_codes`).  All-gather half: the
  owner's segment *sums* are packed at ``Q.sum_wire_bits(bits, n)`` =
  b + ceil(log2 n) bits (`B.pack_sums`) and rotated to every device the
  same way.  Every device then unpacks the full code-sum bucket and
  runs the SAME ``decode_sum_mean``.

  Because int32 code sums are exact in every addition order and the
  shared scale is an order-independent f32 max, the ring is
  BIT-IDENTICAL to the psum wire and to the simulator's
  `grad_compress.compress_allreduce` on any mesh shape — including
  compound (pod, data) axes (``ppermute``/``axis_index`` take the axis
  tuple; rotations act on the flat row-major rank, the same index the
  noise keys fold) and non-power-of-two ring sizes (the last segment is
  ragged and zero-padded; padded rows carry zero codes and are sliced
  off).  That parity is the correctness anchor: the ring lands as a
  pure wire-cost change.

  The log2(n) growth of the all-gather payload is the price of
  exactness — re-quantizing the decoded mean would ship b bits in both
  halves but double-quantizes, breaking the parity anchor (and the
  EF telescoping analysis).  `ring_wire_bytes` models the realized
  bytes precisely; `launch/hlo_cost.py` + tests/test_hlo_cost.py pin
  them against the traced HLO.

* ``ring_ef_reduce_scatter_bucket`` — the ZeRO-sharded wire: the SAME
  ring, stopped at the segment midpoint.  After the reduce-scatter half
  every rank already holds the exact int32 code sum of its OWN segment;
  instead of all-gathering packed sums, each rank decodes just that
  segment's mean (`decode_sum_mean` on one (seg, d) slice) and keeps
  it.  No second collective half at all: the sharded wire ships only
  the n-1 packed b-bit segment hops plus the scale ``pmax``
  (`ring_wire_bytes(..., sharded=True)`), and the downstream optimizer
  is expected to be partitioned to segment owners (see
  `training/pipeline.py` ``dp_wire="ring-sharded"`` and
  `optim/adamw.py::apply_bucket_updates`) with the parameter
  all-gather — which ZeRO-3 performs anyway — closing the loop.
  Because the owned segment's code sum is the SAME exact int32 sum the
  full ring holds at its midpoint, the sharded wire's segment means are
  BIT-IDENTICAL to the corresponding rows of `ef_psum_mean_bucket` /
  `ring_ef_reduce_mean_bucket` / the simulator's
  `grad_compress.compress_reduce_scatter`, including on distinct
  per-rank (local) gradient buckets.  Padded rows of a ragged last
  segment carry zero codes AND a zero scale, so they decode to
  (sign-preserving) zeros on both backends.

Quantization is linear given a *shared* scale, so a sum of codes
dequantizes to the exact mean of the quantized values — the classic
compressed-allreduce construction.  Every quantize/pack/unpack step
routes through `core.boundary`, the backend-selectable fused codec,
never the unfused jnp chain.  `ef_psum_mean_bucket` and the ring add
QuantizedAdam-style error feedback over the bucketed gradient of
`core.grad_compress`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boundary as B
from repro.core import grad_compress as GC
from repro.core import quantization as Q
from repro.core.quantization import _EPS

# Legacy constant: the codec wires THIS module implements.  The
# canonical wire list is the registry (`repro.comm.wires`), which also
# carries wires this module never special-cases (e.g. the fp16
# passthrough) — derive wire choices from there, not from this tuple.
WIRES = ("psum", "ring", "ring-sharded")

# the ONE segment-geometry source (defined next to the bucket layout
# to avoid a circular import; both names are public API)
ring_segment_rows = GC.ring_segment_rows


def ring_chunk_bounds(seg: int, chunks: int) -> tuple:
    """Row bounds that cut one ``seg``-row ring segment into ``chunks``
    chunks — the single chunk-geometry source of the double-buffered
    ring schedule, derived from `ring_segment_rows` itself (chunk width
    = ``ring_segment_rows(seg, chunks)``, the same ceil-division that
    cuts the bucket into segments).

    Returns a tuple of ``(lo, hi)`` half-open row ranges that partition
    ``range(seg)`` exactly: disjoint, covering, in order, with only the
    LAST chunk possibly ragged (shorter).  When ``chunks`` does not
    divide ``seg`` the realized chunk count may be smaller than
    requested (ceil-division minimality) — callers iterate the returned
    bounds, never ``range(chunks)``.

    Invalid chunk counts raise loudly: ``chunks`` must be a positive
    int no larger than ``seg`` (a chunk carries at least one row)."""
    if not isinstance(chunks, int) or isinstance(chunks, bool) \
            or chunks < 1:
        raise ValueError(
            f"chunks={chunks!r} is invalid: the ring chunk count must "
            f"be a positive int — did you mean chunks=1 (the "
            f"monolithic schedule)?")
    if chunks > seg:
        raise ValueError(
            f"chunks={chunks} exceeds the segment's {seg} rows (each "
            f"chunk ships at least one row per hop); valid range is "
            f"1..{seg} — did you mean chunks={seg}?")
    cw = ring_segment_rows(seg, chunks)
    return tuple((lo, min(lo + cw, seg)) for lo in range(0, seg, cw))


def _axis_tuple(axis_name):
    return axis_name if isinstance(axis_name, (tuple, list)) \
        else (axis_name,)


def _flat_axis_index(axis_name):
    """Flat row-major rank along the (possibly compound) DP axis —
    `axis_index` accepts the axis tuple and matches the index
    `_fold_axis_index` folds into the noise keys."""
    axes = _axis_tuple(axis_name)
    return jax.lax.axis_index(axes if len(axes) > 1 else axes[0])


def _fold_axis_index(key, axis_name):
    """Per-device noise key: fold_in the FLAT row-major rank along the
    (possibly compound) DP axis — the same index
    `grad_compress.worker_key` folds for simulated worker i, so
    simulation and wire draw identical noise on any mesh shape."""
    axes = _axis_tuple(axis_name)
    flat = jax.lax.axis_index(axes[0])
    for ax in axes[1:]:
        flat = flat * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return jax.random.fold_in(key, flat)


def quantized_psum_mean(x, axis_name: str, bits: int, key,
                        stochastic: bool = True, *,
                        backend: str = "auto"):
    """Mean of x over `axis_name` with b-bit quantized payload.

    x: (..., d) float; returns f32 of the same shape.  Must be called
    inside shard_map over `axis_name`.  (``psum(1)`` of a Python scalar
    resolves statically from the axis env, so the fused receiver kernel
    gets the device count at trace time and it can never disagree with
    the mesh.)  Uses the codes-only encode — the same single entry
    point as the gradient wires — so there is no on-device pack→unpack
    round trip."""
    n = jax.lax.psum(1, axis_name)
    xf = x.astype(jnp.float32)
    local_s = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = jnp.maximum(jax.lax.pmax(local_s, axis_name), _EPS)
    codes = B.encode_codes_with_scale(xf, s, bits=bits,
                                      stochastic=stochastic, key=key,
                                      backend=backend)
    total = jax.lax.psum(codes, axis_name)
    return B.decode_sum_mean(total, s, bits=bits, n=n, backend=backend)


def ef_psum_mean_bucket(v_grad, err, axis_name, bits: int, key,
                        *, stochastic: bool = True,
                        backend: str = "auto"):
    """Error-feedback compressed allreduce of one gradient bucket
    (psum form: the collective carries i32 lanes — the conservative
    bound the ring improves on).

    v_grad, err: (rows, group_d) f32 — this device's gradient bucket
    (`grad_compress.flatten_bucket`) and carried error.  Returns
    (mean bucket, new error).  Must run inside shard_map over
    `axis_name`; the worker count comes from the axis env itself.

    The noise key is folded by axis position internally, so callers pass
    the same base key on every device."""
    n = jax.lax.psum(1, axis_name)
    v = v_grad.astype(jnp.float32) + err
    s = jnp.maximum(jax.lax.pmax(GC.local_scale(v), axis_name), _EPS)
    _, codes, new_err = GC.ef_encode(
        v, s, bits, _fold_axis_index(key, axis_name),
        stochastic=stochastic, backend=backend)
    total = jax.lax.psum(codes, axis_name)
    mean = B.decode_sum_mean(total, s, bits=bits, n=n, backend=backend)
    return mean, new_err


def _segment(x, j, seg: int):
    """Rows j*seg ... (j+1)*seg of a (n*seg, .) array, j traced."""
    return jax.lax.dynamic_slice_in_dim(x, j * seg, seg, 0)


def _reduce_scatter_codes(packed, codes, n, ax, axis_name, bits,
                          backend):
    """The ring's reduce-scatter half, shared by the full ring and the
    ZeRO-sharded wire: rotate packed code segments to their owners and
    fold each arriving segment into the local int32 accumulator.

    Returns (acc, seg, i): this rank's exact (seg, d) code sum of its
    OWN segment, the segment row count, and the rank's flat ring index.
    Padded rows of a ragged last segment carry zero payload, so they
    accumulate zero sums."""
    rows = codes.shape[0]
    seg = ring_segment_rows(rows, n)
    pad = seg * n - rows
    if pad:
        # zero payload rows: they unpack to zero codes, accumulate to
        # zero sums, and are sliced off (full ring) or decoded against
        # a zero scale (sharded wire) before touching the optimizer
        packed = jnp.pad(packed, ((0, pad), (0, 0)))
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    i = _flat_axis_index(axis_name)

    # segments are row slices of the 2-D arrays, never a (n, seg, .)
    # reshape: on a TPU that reshape is a relayout whenever seg is not a
    # multiple of the row tile, and it compiles in time linear in the
    # rows (minutes for a 1.5B-parameter bucket)
    acc = _segment(codes, i, seg)
    for t in range(1, n):
        perm = [(src, (src + t) % n) for src in range(n)]
        send = _segment(packed, (i + t) % n, seg)
        recv = jax.lax.ppermute(send, ax, perm)
        acc = B.accumulate_codes(recv, acc, bits=bits, backend=backend)
    return acc, seg, i


def make_chunk_encoder(v, s, bits: int, key, n: int, bounds,
                       *, stochastic: bool = True,
                       backend: str = "auto"):
    """Per-chunk encoder for the double-buffered ring, BIT-IDENTICAL to
    the monolithic `grad_compress.ef_encode` sender per row.

    ``v``/``s``: the compensated (rows, group_d) bucket and its shared
    rowwise scale; ``bounds``: `ring_chunk_bounds` output over
    ``seg = ring_segment_rows(rows, n)``.  Returns ``enc(ci)`` mapping
    a chunk index to ``(packed, codes)`` of shape ``(n, cw, ·)`` — the
    packed payload and int32 codes of chunk ``ci``'s rows across ALL
    ``n`` device segments (what the rotation hops slice senders from).

    Bit-parity with the monolithic encode rests on two invariants,
    both regression-gated (tests/test_grad_compress.py,
    tests/test_properties.py):

    * the full-bucket stochastic noise is drawn ONCE here with the
      same ``jax.random.uniform(key, v.shape)`` call the boundary's
      `_noise` makes, then row-sliced per chunk — so every live row
      quantizes against the identical noise value regardless of K
      (the explicit ``noise=`` argument also bypasses the on-core
      PRNG opt-in, whose stream is grid-position-dependent and
      therefore not chunking-invariant);
    * pad rows of a ragged LAST segment are zeroed in code space
      after encoding (a static mask), matching the monolithic path's
      zero-padding of the encoded arrays exactly — quantize(0) under
      a shared scale is NOT zero, so masking must happen after."""
    rows, d = v.shape
    seg = ring_segment_rows(rows, n)
    pad = seg * n - rows
    noise = jax.random.uniform(key, v.shape, jnp.float32) \
        if stochastic else None

    def _padded(a):
        return jnp.pad(a, ((0, pad), (0, 0))) if pad else a

    v3 = _padded(v).reshape(n, seg, d)
    s3 = _padded(s).reshape(n, seg, 1)
    u3 = _padded(noise).reshape(n, seg, d) if stochastic else None

    def enc(ci):
        lo, hi = bounds[ci]
        cw = hi - lo
        vs = v3[:, lo:hi].reshape(n * cw, d)
        ss = s3[:, lo:hi].reshape(n * cw, 1)
        us = u3[:, lo:hi].reshape(n * cw, d) if stochastic else None
        packed, codes = B.encode_codes_with_scale(
            vs, ss, bits=bits, stochastic=stochastic, key=key,
            noise=us, pack=True, backend=backend)
        packed = packed.reshape(n, cw, -1)
        codes = codes.reshape(n, cw, d)
        if pad:
            gidx = np.arange(n)[:, None] * seg \
                + np.arange(lo, hi)[None, :]
            live = gidx < rows
            if not live.all():
                live_j = jnp.asarray(live)[..., None]
                packed = jnp.where(live_j, packed, 0)
                codes = jnp.where(live_j, codes, 0)
        return packed, codes

    return enc


def _chunked_reduce_scatter(v, s, n, ax, axis_name, bits, key,
                            *, stochastic, backend, chunks):
    """The ring's reduce-scatter half, chunked and double-buffered:
    while chunk ``c``'s rotation hops are in flight, chunk ``c+1``
    encodes (the encode is issued between posting the ppermutes and
    consuming their results, so the compiler is free to overlap it
    with the hops).  Ships exactly the same bytes as
    `_reduce_scatter_codes` — chunking changes scheduling, never
    payload — and is bit-identical to it (int32 code sums are exact
    in any order; the encoder is row-sliced, see `make_chunk_encoder`).

    Returns ``(acc, seg, i, new_err)``: the rank's exact (seg, d) own-
    segment code sum, the segment rows, the flat ring index, and the
    error-feedback carry (computed from the reassembled full-bucket
    codes exactly as `grad_compress.ef_encode` does)."""
    rows, d = v.shape
    seg = ring_segment_rows(rows, n)
    bounds = ring_chunk_bounds(seg, chunks)
    enc = make_chunk_encoder(v, s, bits, key, n, bounds,
                             stochastic=stochastic, backend=backend)
    i = _flat_axis_index(axis_name)

    accs, code_chunks = [], []
    packed_c, codes_c = enc(0)
    for ci in range(len(bounds)):
        code_chunks.append(codes_c)
        acc = jax.lax.dynamic_index_in_dim(codes_c, i, 0,
                                           keepdims=False)
        recvs = []
        for t in range(1, n):
            perm = [(src, (src + t) % n) for src in range(n)]
            send = jax.lax.dynamic_index_in_dim(
                packed_c, (i + t) % n, 0, keepdims=False)
            recvs.append(jax.lax.ppermute(send, ax, perm))
        if ci + 1 < len(bounds):
            # double buffer: encode the NEXT chunk while this chunk's
            # hops are in flight (data-independent of the recvs)
            packed_c, codes_c = enc(ci + 1)
        for recv in recvs:
            acc = B.accumulate_codes(recv, acc, bits=bits,
                                     backend=backend)
        accs.append(acc)

    acc = jnp.concatenate(accs, axis=0) if len(accs) > 1 else accs[0]
    codes_full = jnp.concatenate(code_chunks, axis=1) \
        if len(code_chunks) > 1 else code_chunks[0]
    codes_flat = codes_full.reshape(n * seg, d)[:rows]
    q = B.decode_sum_mean(codes_flat, s, bits=bits, n=1,
                          backend=backend)
    return acc, seg, i, v - q


def ring_ef_reduce_scatter_bucket(v_grad, err, axis_name, bits: int, key,
                                  *, stochastic: bool = True,
                                  backend: str = "auto",
                                  chunks: int = 1):
    """ZeRO-sharded error-feedback compressed reduce-scatter: the ring
    stopped at the segment midpoint — each rank keeps only its OWN
    segment's mean; there is no all-gather of sums at all.

    v_grad, err: (rows, group_d) f32 — this rank's (possibly local /
    per-rank-distinct) gradient bucket and carried full-bucket error.
    Returns (own segment mean (seg, group_d) with
    seg = `ring_segment_rows(rows, n)`, new error (rows, group_d)).
    Must run inside shard_map over `axis_name` (a name or axis tuple).

    The owned segment's int32 code sum is the SAME exact sum the full
    ring holds at its midpoint, so the returned rows are bit-identical
    to the corresponding rows of `ring_ef_reduce_mean_bucket` /
    `ef_psum_mean_bucket` and to
    `grad_compress.compress_reduce_scatter` in the simulator.  Rows of
    a ragged last segment beyond the bucket decode against a ZERO
    scale (zero codes, zero scale -> sign-preserving zeros on both
    backends) and must be dropped by the caller before they touch
    parameters — `training/pipeline.py` drops them when unflattening
    the updated parameter bucket.

    Error feedback stays FULL-bucket per rank: every rank encodes its
    whole compensated bucket (it must, to ship every segment to its
    owner), so the carried error is the same (rows, group_d) state the
    other wires carry — only the *reduced gradient* is sharded.

    ``chunks`` > 1 runs the reduce-scatter half chunked and
    double-buffered (`_chunked_reduce_scatter`) — bit-identical,
    byte-identical, scheduling-only; ``chunks=1`` is the exact
    monolithic code path.  Invalid chunk counts raise loudly
    (`ring_chunk_bounds`)."""
    axes = _axis_tuple(axis_name)
    ax = axes if len(axes) > 1 else axes[0]
    n = jax.lax.psum(1, axis_name)
    v = v_grad.astype(jnp.float32) + err
    s = jnp.maximum(jax.lax.pmax(GC.local_scale(v), axis_name), _EPS)
    kf = _fold_axis_index(key, axis_name)
    if chunks != 1:
        # validate loudly even on paths that cannot overlap (n == 1)
        ring_chunk_bounds(ring_segment_rows(v.shape[0], n), chunks)
    if chunks == 1 or n == 1:
        packed, codes, new_err = GC.ef_encode(
            v, s, bits, kf, stochastic=stochastic, backend=backend,
            pack=True)
        if n == 1:
            mean = B.decode_sum_mean(codes, s, bits=bits, n=1,
                                     backend=backend)
            return mean, new_err
        acc, seg, i = _reduce_scatter_codes(packed, codes, n, ax,
                                            axis_name, bits, backend)
    else:
        acc, seg, i, new_err = _chunked_reduce_scatter(
            v, s, n, ax, axis_name, bits, kf, stochastic=stochastic,
            backend=backend, chunks=chunks)
    rows = v.shape[0]
    pad = seg * n - rows
    s_pad = jnp.pad(s, ((0, pad), (0, 0))) if pad else s
    s_own = _segment(s_pad, i, seg)
    seg_mean = B.decode_sum_mean(acc, s_own, bits=bits, n=n,
                                 backend=backend)
    return seg_mean, new_err


def ring_ef_reduce_mean_bucket(v_grad, err, axis_name, bits: int, key,
                               *, stochastic: bool = True,
                               backend: str = "auto",
                               chunks: int = 1):
    """Error-feedback compressed allreduce as a bandwidth-optimal ring:
    packed b-bit codes ship on the wire, accumulation is local.

    Drop-in replacement for `ef_psum_mean_bucket` — same signature,
    BIT-IDENTICAL result on every mesh shape (see module docstring).
    Must run inside shard_map over `axis_name` (a name or an axis
    tuple); the ring size n and the segment schedule resolve statically
    from the axis env.

    Schedule (n = ring size, device i, segment j owned by device j):

      reduce-scatter: for t in 1..n-1, ship MY packed codes of segment
        (i+t) mod n to its owner via the rotation-by-t ppermute; fold
        each arriving segment into my int32 accumulator with the fused
        unpack-accumulate.  After n-1 steps I hold the exact code sum
        of my own segment.
      all-gather: pack my segment sums at b + ceil(log2 n) bits and
        rotate them to every device the same way; unpack all segments
        and decode the mean locally.

    ``chunks`` > 1 chunks and double-buffers the reduce-scatter half
    (`_chunked_reduce_scatter`) — bit-identical, byte-identical,
    scheduling-only; ``chunks=1`` is the exact monolithic code path.
    Invalid chunk counts raise loudly (`ring_chunk_bounds`).
    """
    axes = _axis_tuple(axis_name)
    ax = axes if len(axes) > 1 else axes[0]
    n = jax.lax.psum(1, axis_name)
    v = v_grad.astype(jnp.float32) + err
    s = jnp.maximum(jax.lax.pmax(GC.local_scale(v), axis_name), _EPS)
    kf = _fold_axis_index(key, axis_name)
    if chunks != 1:
        # validate loudly even on paths that cannot overlap (n == 1)
        ring_chunk_bounds(ring_segment_rows(v.shape[0], n), chunks)
    if chunks == 1 or n == 1:
        packed, codes, new_err = GC.ef_encode(
            v, s, bits, kf, stochastic=stochastic, backend=backend,
            pack=True)
        if n == 1:
            mean = B.decode_sum_mean(codes, s, bits=bits, n=1,
                                     backend=backend)
            return mean, new_err
        acc, seg, i = _reduce_scatter_codes(packed, codes, n, ax,
                                            axis_name, bits, backend)
    else:
        acc, seg, i, new_err = _chunked_reduce_scatter(
            v, s, n, ax, axis_name, bits, kf, stochastic=stochastic,
            backend=backend, chunks=chunks)
    rows, d = v.shape

    # ---- all-gather: rotate the packed segment sums to everyone --------
    own = B.pack_sums(acc, bits=bits, n=n, backend=backend)
    gathered = jnp.zeros((n * seg, own.shape[-1]), jnp.uint8)
    gathered = jax.lax.dynamic_update_slice_in_dim(gathered, own, i * seg,
                                                   0)
    for t in range(1, n):
        perm = [(src, (src + t) % n) for src in range(n)]
        recv = jax.lax.ppermute(own, ax, perm)
        gathered = jax.lax.dynamic_update_slice_in_dim(
            gathered, recv, ((i - t) % n) * seg, 0)

    total_p = gathered[:rows]
    total = B.unpack_sums(total_p, bits=bits, n=n, d=d, backend=backend)
    mean = B.decode_sum_mean(total, s, bits=bits, n=n, backend=backend)
    return mean, new_err


def ring_wire_bytes(shape, bits: int, n: int = 2, *,
                    sharded: bool = False, chunks: int = 1) -> int:
    """Collective bytes of the compressed ring for one (rows, d) bucket
    on an n-device ring — exact, matching what `launch/hlo_cost`
    measures on the traced program (tests/test_hlo_cost.py pins this):

    * reduce-scatter: n-1 ppermutes of one packed b-bit segment
      (~ (n-1)/n of the bucket's packed payload per device);
    * all-gather (full ring only): n-1 ppermutes of one packed
      code-SUM segment at b + ceil(log2 n) bits (`Q.sum_wire_bits` —
      the exactness overhead);
    * plus the fp32 scale ``pmax`` (one f32 per bucket row).

    sharded=True models `ring_ef_reduce_scatter_bucket`: the ring
    stopped at the midpoint, so the all-gather term vanishes and only
    the b-bit reduce-scatter hops and the scale pmax remain — strictly
    fewer bytes than the full ring at every b whenever n > 1.

    ``chunks`` is accepted (and validated via `ring_chunk_bounds`)
    because the chunked schedule ships IDENTICAL total bytes: the
    per-hop chunk payloads of one segment sum to exactly the
    monolithic segment payload (packing is per-row, so chunk widths
    add).  tests/test_hlo_cost.py pins the chunked wires' compiled
    collective bytes against this same model.
    """
    rows, d = shape
    seg = ring_segment_rows(rows, n)
    if chunks != 1:
        ring_chunk_bounds(seg, chunks)   # bytes unchanged, validate only
    hops = max(n - 1, 0)
    gather = 0 if sharded else hops * seg * Q.sum_packed_width(d, bits, n)
    return (hops * seg * Q.packed_width(d, bits)
            + gather
            + rows * 4)


# Historical name: pre-ring accounting estimated the compressed psum as
# 2x the packed payload.  Since the ring landed, the realized wire IS
# the ring, so the old entry point resolves to its exact model.
psum_wire_bytes = ring_wire_bytes
