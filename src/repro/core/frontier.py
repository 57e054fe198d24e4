"""Frontier compaction + sparse candidate exchange (O(frontier) supersteps).

The dense engine relaxes all R ELL rows and exchanges O(|V|) candidate
floats per superstep no matter how small the eligible class is — so the
paper's finer orderings (arXiv:1706.05760 §IV) shrink *work* but not
*communication*.  The AGM's workitem sets (arXiv:1604.04772) are
exactly the sparse structure this module recovers, under the TPU
constraint that every shape is static:

* :func:`compact_rows` — ``jnp.where``-style compaction of the eligible
  virtual-row mask into a fixed-capacity index list (cap F, overflow
  flag for the dense fallback), walked by the push relax in chunks of
  :func:`push_chunk` rows,
* :func:`bucket_slots` / :func:`scatter_plane` — per-destination-rank
  slotting of the candidate buffer into fixed-capacity (idx, val)
  buffers,
* :func:`sparse_payload` / :func:`unpack_combine` — the (P, K·S)
  u32 payload moved by one ``all_to_all`` (native indices, then the
  values' and, for KLA, the levels' f32 bits — or packed 16-bit
  round-up value-delta codes in the quantized :data:`PAYLOAD_MODES`)
  and the owner-side scatter-combine back into a dense per-vertex
  array.  Given the device's own rank, both leave its own segment out
  (:func:`remote_ranks`): that segment travels empty and is combined
  on the chip, exact, so on one device nothing is packed or unpacked.

Everything here is collective-free local compute; the engine supplies
the ``all_to_all`` and the global (uniform-across-ranks) fallback
decision.  Capacities are static Python ints fixed at trace time —
:func:`frontier_caps` derives them from the partition shape and the
``frontier_cap`` knob.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)

#: Sparse-exchange payload encodings.  "exact" moves u32 indices + the
#: f32 values' bits (bit-identical to the dense path).  "bf16" /
#: "u16" move u32 indices + 16-bit quantized value *deltas* against
#: each segment's lower bound — round-up-only, so every decoded
#: candidate is >= the exact candidate (inflationary) and the
#: self-stabilizing kernel repairs the error (min-reduce semirings
#: only; the engine enforces this).
PAYLOAD_MODES = ("exact", "bf16", "u16")


def payload_plane_words(
    slot_cap: int, use_level: bool, payload: str = "exact"
) -> int:
    """Axis-1 width, in 32-bit words, of one destination segment of the
    sparse all_to_all payload.

    exact:     [u32 indices | bitcast-f32 values | (bitcast-f32 levels)]
    quantized: [u32 indices | packed u16-pair deltas | lo
                | (scale, u16 only) | (bitcast-f32 levels)]
    """
    S = slot_cap
    if payload == "exact":
        return (3 if use_level else 2) * S
    if payload not in PAYLOAD_MODES:
        raise ValueError(f"unknown payload mode {payload!r}")
    head = 1 if payload == "bf16" else 2  # lo (+ scale)
    return S + (S + 1) // 2 + head + (S if use_level else 0)


def _quantize_bf16(val_buf: jax.Array, lo_fin: jax.Array) -> jax.Array:
    """Round-up bf16 codes for ``val_buf - lo_fin`` (both >= 0 planes).

    The code is the high half of the delta's f32 bits, bumped by one
    when any low bit is set (carry into the exponent is exactly IEEE
    round-toward-+inf, and +inf's code 0x7F80 is a fixed point).  The
    sender then *verifies* its own code with the receiver's decode
    expression; any code that would reconstruct below the exact value
    (the f32 subtraction itself can round down) is replaced by the
    +inf code — a dropped candidate is inflationary-to-+inf and gets
    repaired, never a deflation.
    """
    delta = val_buf - lo_fin[:, None]
    bits = jax.lax.bitcast_convert_type(delta, jnp.uint32)
    carry = (bits & jnp.uint32(0xFFFF)) != jnp.uint32(0)
    q = (bits >> jnp.uint32(16)) + carry.astype(jnp.uint32)
    recon = lo_fin[:, None] + jax.lax.bitcast_convert_type(
        q << jnp.uint32(16), jnp.float32
    )
    return jnp.where(recon < val_buf, jnp.uint32(0x7F80), q)


def _quantize_u16(
    val_buf: jax.Array, lo_fin: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Round-up linear u16 codes + per-segment scale (65535 = +inf).

    ``q = 0`` is pinned to slots whose value *equals* the segment
    lower bound (they decode to ``lo_fin`` bit-exactly, so the
    segment minimum always survives quantization); everything else is
    ceil-scaled with a +1 guard and then sender-verified against the
    receiver's decode expression exactly as in bf16 mode.
    """
    fin = jnp.isfinite(val_buf)
    delta = val_buf - lo_fin[:, None]
    dmax = jnp.max(jnp.where(fin, delta, jnp.float32(0.0)), axis=1)
    scale = jnp.maximum(dmax / jnp.float32(65534.0), jnp.float32(1e-30))
    qf = jnp.ceil(delta / scale[:, None]) + jnp.float32(1.0)
    q = jnp.clip(qf, 0.0, 65534.0).astype(jnp.uint32)
    exact0 = val_buf == lo_fin[:, None]
    q = jnp.where(exact0, jnp.uint32(0), q)
    recon = lo_fin[:, None] + q.astype(jnp.float32) * scale[:, None]
    good = exact0 | (fin & (recon >= val_buf))
    return jnp.where(good, q, jnp.uint32(65535)), scale


def _pack_u16_pairs(q: jax.Array, slot_cap: int) -> jax.Array:
    """Pack (P, S) u16 codes into (P, ceil(S/2)) u32 words, low code
    in the low half."""
    H = (slot_cap + 1) // 2
    qp = jnp.pad(q, ((0, 0), (0, 2 * H - slot_cap)))
    return qp[:, 0::2] | (qp[:, 1::2] << jnp.uint32(16))


def _unpack_u16_pairs(pairs: jax.Array, slot_cap: int) -> jax.Array:
    """Inverse of :func:`_pack_u16_pairs`: (P, ceil(S/2)) -> (P, S)."""
    Pn, H = pairs.shape
    lo = pairs & jnp.uint32(0xFFFF)
    hi = pairs >> jnp.uint32(16)
    return jnp.stack([lo, hi], axis=-1).reshape(Pn, 2 * H)[:, :slot_cap]


def frontier_caps(
    rows: int,
    width: int,
    n_local: int,
    n_parts: int,
    frontier_cap: int | None = None,
) -> tuple[int, int]:
    """Static (row_cap, slot_cap) for the sparse path.

    ``row_cap`` — max eligible virtual rows compacted per device per
    superstep (the knob F; default R/8).  ``slot_cap`` — per-destination
    -rank candidate slots in the sparse exchange, sized so a row_cap
    frontier's candidates spread evenly over ranks fit.  The ELL width
    is ~2x the average degree (graph.partition.default_ell_width), so
    half of F·W is padding by construction and slots are provisioned
    for F·W/(2P); skewed destinations (or denser-than-average
    frontiers) overflow into the dense fallback for that superstep
    instead of corrupting anything.
    """
    if frontier_cap is None:
        row_cap = max(8, rows // 8)
    else:
        row_cap = max(1, int(frontier_cap))
    row_cap = min(rows, row_cap)
    # beyond n_local/2 slots the (idx, val) payload can never move
    # fewer words than the dense reduce-scatter, so cap there and let
    # overflow fall back instead
    slot_cap = max(
        1,
        min(n_local // 2, (row_cap * width) // (2 * max(1, n_parts))),
    )
    return row_cap, slot_cap


#: rows of the compacted frontier that one trip of the push relax's
#: chunk loop gathers and scatter-mins (see :func:`push_chunk`)
PUSH_CHUNK_ROWS = 512


def push_chunk(row_cap: int) -> int:
    """Static rows per trip (K) of the push relax's chunk loop.

    The push relax walks the compacted frontier in chunks of K rows
    and runs ceil(live rows / K) trips, so its gathers and scatter-min
    follow the live frontier rather than ``row_cap``.  A scatter costs
    per update it is handed, live or filled, so a smaller K wastes
    fewer filled rows in the last chunk; a larger K pays the loop's
    per-trip overhead fewer times.
    """
    return min(row_cap, PUSH_CHUNK_ROWS)


def grow_frontier_cap(rows: int, cap: int) -> int:
    """Next rho-stepping row capacity after overflow: double, clamped
    to the per-device ELL row count (beyond which compaction is moot
    and the dense sweep is strictly cheaper)."""
    return min(int(rows), max(1, int(cap)) * 2)


def compact_rows(mask: jax.Array, cap: int):
    """Compact a (R,) bool mask into a capacity-``cap`` index list.

    Returns ``(idx, count, overflow)``: ``idx`` (cap,) int32 holds the
    first ``cap`` set positions in order, padded with the sentinel R
    (one past the last row — gathers fill through it); ``count`` the
    true population; ``overflow`` True iff the mask doesn't fit.
    """
    R = mask.shape[0]
    (idx,) = jnp.nonzero(mask, size=cap, fill_value=R)
    count = jnp.sum(mask.astype(jnp.int32))
    return idx.astype(jnp.int32), count, count > jnp.int32(cap)


def bucket_slots(mask2d: jax.Array, slot_cap: int):
    """Per-destination slot assignment for candidate compaction.

    ``mask2d`` (P, n_local) marks real candidates per destination rank.
    Returns ``(slot, overflow, filled)``: ``slot`` (P, n_local) int32
    gives each candidate its position within destination p's buffer
    (``slot_cap`` for non-candidates and overflow spill — a dropped
    slot); ``overflow`` True iff some destination holds more than
    ``slot_cap`` candidates; ``filled`` the int32 count of slots taken
    over all destinations.
    """
    pos = jnp.cumsum(mask2d.astype(jnp.int32), axis=1) - jnp.int32(1)
    count = pos[:, -1] + jnp.int32(1)  # candidates per destination
    # initial: no destinations (one device, own segment left out)
    overflow = jnp.max(count, initial=0) > jnp.int32(slot_cap)
    filled = jnp.sum(jnp.minimum(count, jnp.int32(slot_cap)))
    slot = jnp.where(
        mask2d & (pos < jnp.int32(slot_cap)), pos, jnp.int32(slot_cap)
    )
    return slot, overflow, filled


def scatter_plane(vals2d: jax.Array, slot: jax.Array, slot_cap: int, fill,
                  dest=None, n_dest=None):
    """Scatter (P, n_local) values into their (P, slot_cap) buffer
    positions; slot ``slot_cap`` is a discarded spill column.

    ``dest`` (rows of ``vals2d``,) names each row's buffer row among
    ``n_dest``; rows no ``dest`` names keep ``fill``.  Default: row i
    of ``vals2d`` fills buffer row i.
    """
    if dest is None:
        dest = jnp.arange(vals2d.shape[0], dtype=jnp.int32)
        n_dest = vals2d.shape[0]
    rows = jnp.broadcast_to(dest[:, None], vals2d.shape)
    buf = jnp.full((n_dest, slot_cap + 1), fill, vals2d.dtype)
    return buf.at[rows, slot].set(vals2d, mode="drop")[:, :slot_cap]


def remote_ranks(n_parts: int, me: jax.Array) -> jax.Array:
    """The (P-1,) ranks other than ``me``, in order: the destinations
    whose candidates must leave the device (none on one device)."""
    j = jnp.arange(n_parts - 1, dtype=jnp.int32)
    return j + (j >= me).astype(jnp.int32)


def sparse_payload(
    C: jax.Array,
    extra_planes,
    n_parts: int,
    slot_cap: int,
    worst,
    payload: str = "exact",
    self_rank=None,
):
    """Build the per-destination all_to_all payload from the (n_pad,)
    local candidate buffer ``C``.

    The payload is u32 words, axis-1 layout [indices | values | extra
    planes...].  Indices are native integers and every f32 plane rides
    as its bits, so nothing in transit is a float: a local index below
    2^23 bitcast into f32 would be a denormal, which a float path that
    flushes denormals to zero turns into index 0.  ``extra_planes`` is
    a list of ``(array, fill)`` pairs of (n_pad,) f32 attributes riding
    along (the KLA level).

    ``payload="exact"`` (default): the values plane is the f32 bits of
    the candidates — bit-identical to the dense exchange.

    ``payload="bf16"`` / ``"u16"``: the values plane is packed 16-bit
    value-delta codes and the segment lower bound (+ scale for u16).
    Indices stay full-width (the payload-overflow lint's invariant:
    quantize values, never indices); values are round-up-only deltas,
    so decoded candidates are >= the exact ones and self-stabilization
    repairs them.  Requires a min-reduce semiring with ``worst == +inf``
    (the engine enforces).

    ``self_rank`` (the device's flat rank, traced): its own segment of
    ``C`` stays out of the payload — only the P-1 remote segments are
    bucketed, counted, checked for overflow and scattered, and row
    ``self_rank`` travels empty.  :func:`unpack_combine` with the same
    rank combines that segment on the device instead.  ``None`` (the
    default) packs all P segments.

    Returns ``(payload, overflow, filled)`` (:func:`bucket_slots`'
    overflow and filled-slot count); empty slots carry ``worst`` values
    and the index sentinel n_local (the owner's discarded dummy slot).
    """
    if payload not in PAYLOAD_MODES:
        raise ValueError(f"unknown payload mode {payload!r}")
    Pn = n_parts
    n_local = C.shape[0] // Pn
    if self_rank is None:
        dest = jnp.arange(Pn, dtype=jnp.int32)
    else:
        dest = remote_ranks(Pn, self_rank)

    def segments(x):  # the (destinations, n_local) segments packed
        x2 = x.reshape(Pn, n_local)
        return x2 if self_rank is None else x2[dest]

    C2 = segments(C)
    slot, overflow, filled = bucket_slots(C2 != worst, slot_cap)
    lidx = jnp.broadcast_to(
        jnp.arange(n_local, dtype=jnp.uint32)[None, :], C2.shape
    )

    def plane(vals2d, fill):
        return scatter_plane(vals2d, slot, slot_cap, fill, dest, Pn)

    idx_buf = plane(lidx, jnp.uint32(n_local))
    val_buf = plane(C2, jnp.float32(worst))
    words = [idx_buf]
    if payload == "exact":
        words.append(jax.lax.bitcast_convert_type(val_buf, jnp.uint32))
    else:
        lo = jnp.min(val_buf, axis=1)  # per-destination-segment lower bound
        lo_fin = jnp.where(jnp.isfinite(lo), lo, jnp.float32(0.0))
        if payload == "bf16":
            q = _quantize_bf16(val_buf, lo_fin)
            head = [lo]
        else:
            q, scale = _quantize_u16(val_buf, lo_fin)
            head = [lo, scale]
        words += [
            _pack_u16_pairs(q, slot_cap),
            jax.lax.bitcast_convert_type(jnp.stack(head, axis=1),
                                         jnp.uint32),
        ]
    for arr, fill in extra_planes:
        lvl_buf = plane(segments(arr), jnp.float32(fill))
        words.append(jax.lax.bitcast_convert_type(lvl_buf, jnp.uint32))
    return jnp.concatenate(words, axis=1), overflow, filled


def unpack_combine(
    recv: jax.Array,
    n_local: int,
    slot_cap: int,
    is_min: bool,
    worst,
    has_level: bool,
    payload: str = "exact",
    self_rank=None,
    own=None,
):
    """Owner-side combine of a received (P, K·S) payload.

    Returns ``(mine, mineL)``: the (n_local,) combined candidate per
    owned vertex and, when ``has_level``, the minimum level among
    candidates matching the winning value (the dense path's
    deterministic tie-break); ``mineL`` is None otherwise.

    ``self_rank`` with ``own = (C_self, CL_self)``: row ``self_rank``
    of ``recv`` (the device's own, empty — see :func:`sparse_payload`)
    is skipped, and the device's own (n_local,) candidates and their
    levels (``CL_self`` is None without ``has_level``) seed the
    combine, so only the P-1 remote rows are scattered.  Min and max
    are order-free, so the result is bit-identical to packing the own
    segment too, and exact there even for quantized payloads.

    For quantized payloads the codes are decoded with the *same*
    expression the sender verified against, so every decoded value is
    exactly the sender's reconstruction: >= the exact candidate, equal
    at each segment's lower bound.
    """
    if payload not in PAYLOAD_MODES:
        raise ValueError(f"unknown payload mode {payload!r}")
    if self_rank is not None:
        recv = recv[remote_ranks(recv.shape[0], self_rank)]
    S = slot_cap
    idx = recv[:, :S].astype(jnp.int32)
    if payload == "exact":
        val = jax.lax.bitcast_convert_type(recv[:, S : 2 * S], jnp.float32)
        lvl_base = 2 * S
    else:
        H = (S + 1) // 2
        q = _unpack_u16_pairs(recv[:, S : S + H], S)
        lo = jax.lax.bitcast_convert_type(recv[:, S + H], jnp.float32)
        lo_fin = jnp.where(jnp.isfinite(lo), lo, jnp.float32(0.0))
        if payload == "bf16":
            # the +inf code 0x7F80 decodes to lo_fin + inf = +inf
            val = lo_fin[:, None] + jax.lax.bitcast_convert_type(
                q << jnp.uint32(16), jnp.float32
            )
            lvl_base = S + H + 1
        else:
            scale = jax.lax.bitcast_convert_type(
                recv[:, S + H + 1], jnp.float32
            )
            val = jnp.where(
                q == jnp.uint32(65535),
                INF,
                lo_fin[:, None] + q.astype(jnp.float32) * scale[:, None],
            )
            lvl_base = S + H + 2
    if self_rank is None:
        buf = jnp.full((n_local + 1,), worst, jnp.float32)
    else:
        buf = jnp.concatenate([own[0], jnp.full((1,), worst, jnp.float32)])
    flat_i, flat_v = idx.reshape(-1), val.reshape(-1)
    buf = buf.at[flat_i].min(flat_v) if is_min else buf.at[flat_i].max(flat_v)
    mine = buf[:n_local]
    if not has_level:
        return mine, None
    lvl = jax.lax.bitcast_convert_type(
        recv[:, lvl_base : lvl_base + S], jnp.float32
    )
    win = val == buf[idx]  # sentinel slots: worst == worst, lvl fill = inf
    if self_rank is None:
        lbuf = jnp.full((n_local + 1,), INF, jnp.float32)
    else:
        # the own candidates' levels where they win, as their slots would
        C_self, CL_self = own
        own_win = (C_self == mine) & (C_self != worst)
        lbuf = jnp.concatenate([jnp.where(own_win, CL_self, INF),
                                jnp.full((1,), INF, jnp.float32)])
    lbuf = lbuf.at[flat_i].min(jnp.where(win, lvl, INF).reshape(-1))
    return mine, lbuf[:n_local]
