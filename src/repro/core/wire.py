"""Flat wire-buffer codec substrate: flatten once, compress flat, unflatten once.

Every compressor in core/compression.py encodes/decodes a SINGLE contiguous
1-D fp32 buffer — the layout a real compressed all-reduce transmits, and the
layout the Pallas kernels (kernels/zsign, kernels/efsign) consume directly.
The round engine (core/fedavg.py) flattens the pseudo-gradient pytree exactly
once per client via :class:`TreeSpec`, and unflattens the decoded server
estimate exactly once per round. Nothing in between ever sees a pytree.

Key pieces:

  ``TreeSpec``     cached flatten metadata (treedef + leaf shapes/offsets).
                   Built at trace time; ``flatten``/``unflatten`` are the only
                   tree <-> buffer conversions in the whole round step.
  ``WireFormat``   what actually crosses the network for one client:
                   wire dtype, bits per coordinate, payload layout name.
  ``pack_signs`` / ``unpack_signs``
                   the pure-jnp 8:1 bitpack shared by every sign-family
                   compressor (the Pallas kernel in kernels/zsign is the
                   fused fast path, bit-for-bit identical — see tests).
  ``unpack_sum`` / ``unpack_sum_mask``
                   the server side of the 1-bit uplink: weighted sign sum
                   computed directly on the packed bytes (butterfly bit-
                   transpose, then weighted-LUT gather / popcount), never
                   materializing the dense (n_clients, d) fp32 sign matrix.
                   These are the CPU paths; the Pallas ``sign_reduce`` kernel
                   (kernels/zsign) is the TPU fast path, bit-identical by
                   construction (same blocked client accumulation order).

Wire-size accounting: ``WireFormat.bits_per_coord`` is the *logical* cost per
model coordinate (1.0 for bitpacked signs, 32.0 for dense fp32, 64*frac for
COO top-k). Uplink metrics multiply it by the true coordinate count
``TreeSpec.n_coords``, not the padded buffer length, so padding to the pack
boundary (8) or the kernel tile (8192) never inflates reported bits.

Buffers may be longer than ``n_coords`` (pack/tile padding); ``unflatten``
reads only the leading ``n_coords`` entries, so decoders can hand back padded
buffers unsliced.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import checkify


@dataclasses.dataclass(frozen=True)
class WireFormat:
    """Describes one client's uplink payload.

    dtype:          numpy-style name of the dtype on the wire ("uint8" for
                    bitpacked signs, "float32" for dense).
    bits_per_coord: logical uplink bits per model coordinate (excludes
                    padding; includes per-tensor side info such as the EF
                    scale, which is O(1) and amortizes to ~0 per coord).
    layout:         payload layout name — "dense" | "bitpacked" |
                    "bitpacked+scale" | "sparse_coo".
    """
    dtype: str
    bits_per_coord: float
    layout: str


@dataclasses.dataclass(frozen=True)
class TreeSpec:
    """Flatten-once metadata for a fixed pytree structure.

    Holds the treedef plus per-leaf (shape, offset) so ``flatten`` and
    ``unflatten`` are single concatenate / slice+reshape passes. Construction
    happens at trace time (shapes are static), so the spec costs nothing
    inside jit.
    """
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    offsets: Tuple[int, ...]
    n_coords: int

    @classmethod
    def from_tree(cls, tree) -> "TreeSpec":
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shapes, offsets, off = [], [], 0
        for l in leaves:
            shapes.append(tuple(l.shape))
            offsets.append(off)
            n = 1
            for d in l.shape:
                n *= int(d)
            off += n
        return cls(treedef=treedef, shapes=tuple(shapes),
                   offsets=tuple(offsets), n_coords=off)

    def flatten(self, tree) -> jax.Array:
        """pytree -> (n_coords,) float32 buffer (the one flatten per round)."""
        leaves = jax.tree_util.tree_leaves(tree)
        return jnp.concatenate(
            [l.astype(jnp.float32).reshape(-1) for l in leaves])

    def unflatten(self, flat: jax.Array):
        """(>= n_coords,) buffer -> pytree of float32 leaves.

        Accepts padded buffers: only the leading ``n_coords`` entries are
        read, so sign decoders never need to slice off pack/tile padding.
        """
        leaves = []
        for shape, off in zip(self.shapes, self.offsets):
            n = 1
            for d in shape:
                n *= d
            leaves.append(jax.lax.dynamic_slice_in_dim(flat, off, n)
                          .reshape(shape))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def tree_spec(tree) -> TreeSpec:
    return TreeSpec.from_tree(tree)


# ---------------------------------------------------------------------------
# sign bitpacking (pure-jnp reference path, little-endian bit order; the
# Pallas kernel in kernels/zsign produces the identical byte stream)
# ---------------------------------------------------------------------------

def pack_bool(bits: jax.Array) -> jax.Array:
    """bool (flat, len % 8 == 0) -> uint8 bitfield of len/8.

    THE little-endian pack every sign path shares: element 8i+j lands in bit
    j of byte i. The Pallas kernels compute the same bytes on the MXU
    (kernels/common ``pack_bits``) — bit-exactness between the two is pinned
    by the encode-equivalence tests.
    """
    b = bits.astype(jnp.uint8).reshape(-1, 8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(b * weights, axis=-1, dtype=jnp.uint8)


def pack_signs(signs_i8: jax.Array) -> jax.Array:
    """int8 {-1,+1} (flat, len % 8 == 0) -> uint8 bitfield of len/8."""
    return pack_bool(signs_i8 > 0)


def unpack_signs(packed: jax.Array) -> jax.Array:
    """uint8 bitfield -> int8 {-1,+1} of len*8."""
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    bits = (packed[:, None] & weights) > 0
    return jnp.where(bits, jnp.int8(1), jnp.int8(-1)).reshape(-1)


def pad_to(x: jax.Array, mult: int) -> jax.Array:
    r = (-x.shape[0]) % mult
    return jnp.pad(x, (0, r)) if r else x


def pack_flat(flat: jax.Array) -> jax.Array:
    """(d,) f32 -> bitpacked uint8 of ceil(d/8): bit = flat[i] >= 0.

    Zero-padded tail packs as +1 bits; harmless because ``TreeSpec.unflatten``
    never reads past n_coords.
    """
    y = pad_to(flat, 8)
    return pack_signs(jnp.where(y >= 0, jnp.int8(1), jnp.int8(-1)))


# Clients per accumulation block. MUST match kernels/zsign/zsign.CLIENT_BLK:
# the jnp fallback below accumulates in the same blocked client order as the
# Pallas sign_reduce kernel, so the CPU path and the TPU kernel produce
# bit-identical f32 sums for ANY per-client weights (not just 0/1 masks).
# A stack under 8 clients is one block of its own size in the kernel and
# one zero-padded block of 8 here: the same in-block left fold, as the
# zero-weight terms change at most the sign of a zero.
SIGN_REDUCE_CLIENT_BLK = 8


def _bit_transpose_blocks(pm: jax.Array, n_blocks: int,
                          n_bytes: int) -> jax.Array:
    """(n_blocks*8, n_bytes) u8 -> (n_blocks, 8, n_bytes) u8 bitplanes.

    Three butterfly stages (Hacker's Delight 7-3, vectorized over all bytes)
    transpose each block's 8x8 bit tile: plane k's byte j holds, in bit i,
    bit k of client i's byte j — i.e. one byte now carries 8 CLIENTS' bits
    for a single coordinate. ~24 u8 passes over the wire bytes, no
    per-coordinate expansion.
    """
    u8 = jnp.uint8
    x = pm.reshape(n_blocks, 2, 2, 2, n_bytes)
    t, b = x[:, 0], x[:, 1]
    x = jnp.stack([(t & u8(0x0F)) | ((b & u8(0x0F)) << 4),
                   ((t & u8(0xF0)) >> 4) | (b & u8(0xF0))], axis=1)
    t, b = x[:, :, 0], x[:, :, 1]
    x = jnp.stack([(t & u8(0x33)) | ((b & u8(0x33)) << 2),
                   ((t & u8(0xCC)) >> 2) | (b & u8(0xCC))], axis=2)
    t, b = x[:, :, :, 0], x[:, :, :, 1]
    x = jnp.stack([(t & u8(0x55)) | ((b & u8(0x55)) << 1),
                   ((t & u8(0xAA)) >> 1) | (b & u8(0xAA))], axis=3)
    return x.reshape(n_blocks, 8, n_bytes)


def _block_luts(wb: jax.Array) -> jax.Array:
    """(n_blocks, blk) f32 weight blocks -> (n_blocks, 256) weighted-sign
    tables ``LUT[v] = sum_i (bit i of v ? +w_i : -w_i)`` — the in-block
    8-client reduce, performed once per block at table-build time in client
    order (the order the Pallas kernel and the dense oracle share)."""
    v = jnp.arange(256, dtype=jnp.uint8)
    vbits = ((v[:, None] >> jnp.arange(8, dtype=jnp.uint8))
             & jnp.uint8(1)) > 0                            # (256, 8)
    return jnp.sum(jnp.where(vbits[None], wb[:, None, :], -wb[:, None, :]),
                   axis=-1)                                 # (n_blocks, 256)


def unpack_sum(packed: jax.Array, weights: jax.Array,
               acc: "jax.Array | SignFoldAcc | None" = None) -> jax.Array:
    """(n_clients, n_bytes) u8, (n_clients,) f32 -> (8*n_bytes,) weighted sum
    of the +/-1 signs — the server side of the 1-bit all-gather.

    LUT over transposed bitplanes: each block of 8 clients is bit-TRANSPOSED
    (``_bit_transpose_blocks``) so one byte holds the block's 8 sign bits for
    a single coordinate, then a per-block 256-entry table
    ``LUT[v] = sum_i (bit i of v ? +w_i : -w_i)`` turns the weighted
    8-client reduce into one cache-resident gather per coordinate. The dense
    (n_clients, d) fp32 sign matrix (32 bits/coord/client) that the
    pre-fused server decode materialized never exists — the fp32 working set
    is the output-sized accumulator only. Dead clients (weight 0) contribute
    exactly 0.

    ``acc`` is the partial-accumulator FOLD hook for the streaming cohort
    driver, in one of two forms:

      * an (8*n_bytes,) f32 running sum from previous client shards,
        continued as the flat left fold ``((acc + b_0) + b_1) + ...`` over
        this call's client blocks. Bit-identical to one call over the
        concatenated clients whenever (a) the weights are a 0/1 mask
        (integer sums — exact under any association) or (b) every shard is
        a multiple of SIGN_REDUCE_CLIENT_BLK clients (identical block
        boundaries AND identical left-fold order, any fp32 weights), up to
        the sign of f32 zeros. Off-block shard sizes shift the 8-client
        block boundaries and therefore re-associate the fp32 sums.
      * a :class:`SignFoldAcc` (from :func:`sign_fold_init`): the
        shard-partition-INVARIANT fold. Sub-block client remainders are
        buffered as pending wire rows instead of closing a misaligned
        block, so the global 8-client block boundaries — and the exact
        fp32 addition order — match the single concatenated call for ANY
        shard partition and any fp32 weights. The return value is the
        updated SignFoldAcc; :func:`sign_fold_finalize` flushes the last
        partial block and yields the (8*n_bytes,) sum, bit-identical to
        the one-shot call (zero signs included).

    Accumulation order mirrors the Pallas ``sign_reduce`` kernel: clients
    are padded to SIGN_REDUCE_CLIENT_BLK with zero weight, the in-block
    8-element reduce happens at LUT build time in client order, and block
    partials are added sequentially onto ``acc`` — bit-exact vs the kernel,
    carried sum included, for ANY fp32 weights up to the sign of a zero
    (verified in tests/test_sign_reduce.py), exact vs any order for 0/1
    masks (integer sums), and within 1 ulp/client of the legacy dense path
    (``unpack_sum_dense``).
    """
    if isinstance(acc, SignFoldAcc):
        return _sign_fold_step(packed, weights, acc)
    n, n_bytes = packed.shape
    blk = SIGN_REDUCE_CLIENT_BLK
    cpad = (-n) % blk
    if cpad:
        packed = jnp.pad(packed, ((0, cpad), (0, 0)))
    w = weights.astype(jnp.float32)
    if cpad:
        w = jnp.pad(w, (0, cpad))
    n_blocks = (n + cpad) // blk
    planes = _bit_transpose_blocks(packed, n_blocks, n_bytes)
    lut = _block_luts(w.reshape(n_blocks, blk))             # (n_blocks, 256)
    if acc is None:
        a = jnp.take(lut[0], planes[0].astype(jnp.int32), axis=0)  # (8, nb)
        start = 1
    else:
        # resume the left fold from the carried partial sum (inverse of the
        # output layout below: coordinate byte*8 + k lives at [k, byte])
        a = jnp.swapaxes(acc.reshape(n_bytes, 8), 0, 1)
        start = 0
    for b in range(start, n_blocks):
        a = a + jnp.take(lut[b], planes[b].astype(jnp.int32), axis=0)
    # a[k, byte] is the weighted sum for coordinate byte*8 + k
    return jnp.swapaxes(a, 0, 1).reshape(-1)


class SignFoldAcc(NamedTuple):
    """Shard-partition-invariant carry for the fp32-weighted sign fold.

    The flat ``acc`` fold of :func:`unpack_sum` closes an 8-client LUT block
    at every shard boundary, so a shard size that is not a multiple of
    SIGN_REDUCE_CLIENT_BLK shifts the block boundaries and re-associates the
    fp32 additions — the historical "bit-identical only at shard % 8 == 0"
    caveat. This carry removes the caveat structurally: clients that do not
    fill a block are PARKED as pending wire rows (bytes + weights) and the
    block is only closed — in global client order — once 8 rows exist, so
    the fold replays the exact addition sequence of the single concatenated
    call no matter how the client stream is partitioned.

    Bit-exactness bookkeeping: ``sums`` starts at -0.0 (the IEEE-754
    additive identity that preserves the bit pattern of every float,
    including +/-0.0), and deferred / absent blocks contribute a -0.0 term
    instead of being skipped, so every closed block enters the sum exactly
    once and in the same order as the one-shot call — the finalized result
    is bit-identical, zero signs included.

    Fields:
      sums        (8, n_bytes) f32 — closed-block partial sums in the
                  bit-transposed layout (coordinate byte*8 + k at [k, byte])
      pend_bytes  (SIGN_REDUCE_CLIENT_BLK, n_bytes) u8 — buffered wire rows
                  of the open block; rows >= pend_n are zero
      pend_w      (SIGN_REDUCE_CLIENT_BLK,) f32 — their weights (same rule)
      pend_n      () int32 — number of valid pending rows, 0..7

    A NamedTuple, hence a pytree: it rides through ``lax.scan`` carries,
    ``jax.jit`` boundaries and ``shard_map`` bodies unchanged. It must be
    finalized (:func:`sign_fold_finalize`) BEFORE any cross-device psum —
    pending rows are positional, not additive.
    """
    sums: jax.Array
    pend_bytes: jax.Array
    pend_w: jax.Array
    pend_n: jax.Array


def sign_fold_init(n_bytes: int) -> SignFoldAcc:
    """Fresh partition-invariant fold carry for an (.., n_bytes) wire row."""
    blk = SIGN_REDUCE_CLIENT_BLK
    return SignFoldAcc(
        sums=jnp.full((8, n_bytes), -0.0, jnp.float32),
        pend_bytes=jnp.zeros((blk, n_bytes), jnp.uint8),
        pend_w=jnp.zeros((blk,), jnp.float32),
        pend_n=jnp.zeros((), jnp.int32))


def _sign_fold_step(packed: jax.Array, weights: jax.Array,
                    acc: SignFoldAcc) -> SignFoldAcc:
    """Fold one shard of (k, n_bytes) wire rows into the carry.

    The pending rows (0..7 of them) are placed at the head of a zero
    buffer, the shard's rows behind them at the traced offset ``pend_n``;
    every COMPLETE 8-row block of the buffer is closed in order (incomplete
    trailing rows add a bit-preserving -0.0 instead), and the remainder is
    sliced back out as the new pending block. The buffer is sized so
    neither the dynamic_update_slice nor the trailing dynamic_slice can
    clamp: B = ((7 + k) // 8 + 1) * 8 >= pend_n + k + 1 and >= s + 8 for
    the remainder start s = ((pend_n + k) // 8) * 8.
    """
    k, n_bytes = packed.shape
    blk = SIGN_REDUCE_CLIENT_BLK
    n_blocks = (blk - 1 + k) // blk + 1
    buf_rows = n_blocks * blk
    buf = jnp.zeros((buf_rows, n_bytes), jnp.uint8).at[:blk].set(
        acc.pend_bytes)
    wbuf = jnp.zeros((buf_rows,), jnp.float32).at[:blk].set(acc.pend_w)
    buf = jax.lax.dynamic_update_slice(buf, packed, (acc.pend_n, 0))
    wbuf = jax.lax.dynamic_update_slice(
        wbuf, weights.astype(jnp.float32), (acc.pend_n,))
    total = acc.pend_n + k
    planes = _bit_transpose_blocks(buf, n_blocks, n_bytes)
    lut = _block_luts(wbuf.reshape(n_blocks, blk))
    neg0 = jnp.full((8, n_bytes), -0.0, jnp.float32)
    a = acc.sums
    for b in range(n_blocks):
        contrib = jnp.take(lut[b], planes[b].astype(jnp.int32), axis=0)
        a = a + jnp.where((b + 1) * blk <= total, contrib, neg0)
    start = (total // blk) * blk
    return SignFoldAcc(
        sums=a,
        pend_bytes=jax.lax.dynamic_slice(buf, (start, 0), (blk, n_bytes)),
        pend_w=jax.lax.dynamic_slice(wbuf, (start,), (blk,)),
        pend_n=total % blk)


def sign_fold_finalize(acc: SignFoldAcc) -> jax.Array:
    """Close the open block and return the (8*n_bytes,) weighted sign sum —
    bit-identical to one :func:`unpack_sum` call over the concatenated
    clients (whose trailing partial block is zero-padded exactly like the
    pending buffer). A carry with no pending rows adds -0.0, a bitwise
    no-op."""
    n_bytes = acc.pend_bytes.shape[1]
    planes = _bit_transpose_blocks(acc.pend_bytes, 1, n_bytes)
    lut = _block_luts(acc.pend_w.reshape(1, -1))
    contrib = jnp.take(lut[0], planes[0].astype(jnp.int32), axis=0)
    neg0 = jnp.full((8, n_bytes), -0.0, jnp.float32)
    a = acc.sums + jnp.where(acc.pend_n > 0, contrib, neg0)
    return jnp.swapaxes(a, 0, 1).reshape(-1)


def check_mask_membership(mask: jax.Array) -> None:
    """Runtime assertion of the 0/1 membership contract (debug-wire mode).

    The popcount/vote paths are only correct for masks that are EXACTLY 0.0
    or 1.0 per entry — the static ``weights_are_mask`` guarantee. This is the
    dynamic counterpart, inserted when ``RoundContext(debug_wire=True)`` (or
    ``REPRO_DEBUG_WIRE=1``) is set: a ``checkify.check`` over the traced mask
    values. Called eagerly it raises immediately on violation; under ``jit``
    the caller must functionalize the check, i.e. wrap the jitted step as
    ``err, out = checkify.checkify(jax.jit(step))(...); err.throw()`` — the
    train launcher and the CI attacks job do exactly that. A bare
    ``jax.jit`` around a debug-wire step fails at trace time with checkify's
    "not functionalized" error, which is intentional: debug mode refuses to
    run unchecked.
    """
    m = jnp.asarray(mask)
    ok = jnp.all((m == 0.0) | (m == 1.0))
    checkify.check(ok, "debug_wire: mask violates the 0/1 membership "
                       "contract required by the popcount/vote paths "
                       "(weights_are_mask) — found fractional or negative "
                       "weights. Use weights_are_mask=False (LUT path) for "
                       "weighted aggregation.")


def unpack_sum_mask(packed: jax.Array, mask: jax.Array,
                    acc: jax.Array | None = None, *,
                    debug: bool = False) -> jax.Array:
    """(n_clients, n_bytes) u8, (n_clients,) 0/1 mask -> (8*n_bytes,) f32
    masked sum of the +/-1 signs — the popcount fast path.

    For membership weights the weighted sum collapses to an integer bit
    count: sum_live(2b - 1) = 2*count - n_live. The count is computed
    entirely in the uint8 wire domain: dead clients' bytes are zeroed, each
    block of 8 clients is bit-TRANSPOSED in three butterfly stages (Hacker's
    Delight 7-3, vectorized over all bytes) so one byte holds 8 clients'
    bits for a single coordinate, then ``lax.population_count`` + a tiny
    cross-block add yield the per-coordinate count. ~24 u8 passes over the
    wire bytes total — no per-coordinate expansion to int8/fp32 at all. Exact
    by construction (integer counts), so it is bit-identical to
    ``unpack_sum``, ``unpack_sum_dense`` and the Pallas kernel for any 0/1
    mask.

    The mask is treated as MEMBERSHIP (w > 0 participates); fractional
    weights must use :func:`unpack_sum`. ``acc`` folds a running partial sum
    from previous client shards (streaming cohort driver); because every
    term is a small integer, the shard-by-shard fold is bit-identical to
    one call over the concatenated clients for ANY shard size.

    Because the membership contract cannot be
    checked on traced values, dispatch here is gated on a STATIC guarantee
    plumbed from whoever constructs the mask: the round engine's
    ``RoundContext(weights_are_mask=True)`` (set by the train launcher,
    whose participation sampler emits exact 0/1) flips the
    sign-family compressors' flag and ``compression.sign_reduce`` then
    routes its jnp backend through this popcount path. Weighted calls (EF
    mask * scale, data-size weights) keep the LUT path. ``debug=True`` adds
    the dynamic membership assertion (:func:`check_mask_membership`) on top
    of the static gate.
    """
    if debug:
        check_mask_membership(mask)
    bitsum = _mask_bit_count(packed, mask).astype(jnp.float32)
    out = 2.0 * bitsum - jnp.sum(mask)
    return out if acc is None else acc + out


def _mask_bit_count(packed: jax.Array, mask: jax.Array) -> jax.Array:
    """(n_clients, n_bytes) u8 + (n_clients,) 0/1 mask -> (8*n_bytes,)
    per-coordinate count of set bits across live clients (integer dtype).

    The shared popcount core of :func:`unpack_sum_mask` and
    :func:`vote_accumulator`. The cross-block accumulator stays uint8 only
    while EVERY physically settable bit fits: after zero-padding clients to
    the 8-row block boundary there are ``n + (-n) % 8`` block rows, and
    although the pad rows are zeroed today, the safe bound is the padded row
    count — u8 accumulation is used only when ``n + (-n) % 8 <= 255``
    (i.e. n <= 248), int32 otherwise. (The previous ``n <= 255`` bound
    leaned on the pad rows staying zero; this one is safe for any bit the
    buffer can hold. Regression-pinned at the boundary in
    tests/test_sign_reduce.py.)
    """
    n, n_bytes = packed.shape
    pm = packed * (mask > 0).astype(jnp.uint8)[:, None]
    cpad = (-n) % 8
    if cpad:
        pm = jnp.pad(pm, ((0, cpad), (0, 0)))
    n_blocks = (n + cpad) // 8
    planes = _bit_transpose_blocks(pm, n_blocks, n_bytes)
    cnt = jax.lax.population_count(planes)          # (blocks, 8, n_bytes) u8
    acc_dtype = jnp.uint8 if n + cpad <= 255 else jnp.int32
    c = jnp.sum(cnt, axis=0, dtype=acc_dtype) if n_blocks > 1 else cnt[0]
    # c[k, byte] counts set bit-k across live clients; coord = byte*8 + k
    return jnp.swapaxes(c, 0, 1).reshape(-1)


#: Robust sign-aggregation modes decodable from the (signed_count, n_live)
#: vote pair — see :func:`vote_accumulator` / :func:`vote_decode`.
VOTE_AGG_MODES = ("mean", "vote", "trimmed", "median")


def vote_accumulator(packed: jax.Array, mask: jax.Array,
                     acc: jax.Array | None = None, *,
                     debug: bool = False) -> jax.Array:
    """(n_clients, n_bytes) u8 + (n_clients,) 0/1 mask -> (2, 8*n_bytes)
    int32 VOTE PAIR: row 0 the per-coordinate signed vote count
    ``s = sum_live sign_i`` (= 2*count - n_live), row 1 the live count
    ``n_live`` (broadcast per coordinate).

    The integer sufficient statistic for EVERY robust sign aggregate: for
    +/-1 votes, mean, majority vote, coordinate-wise trimmed(f) mean, and
    coordinate-wise median are all closed-form post-processings of
    ``(s, n_live)`` — see :func:`vote_decode`. Because both rows are plain
    integer SUMS over clients, the pair

      * folds additively across streamed client shards (``acc`` carries the
        running pair; bit-exact for any shard size — integer arithmetic),
      * crosses devices in the SAME single ``lax.psum`` as the mean path
        (:func:`psum_accumulator` on the int32 pair, O(2d) on the wire),
      * never inflates to an (n_clients, d) matrix — same ~24 u8 passes as
        :func:`unpack_sum_mask` plus one subtract.

    Requires the 0/1 membership contract (weights_are_mask); fractional
    weights have no integer vote-count semantics. ``debug=True`` adds the
    dynamic assertion of that contract.
    """
    if debug:
        check_mask_membership(mask)
    bitsum = _mask_bit_count(packed, mask).astype(jnp.int32)
    n_live = jnp.sum(mask).astype(jnp.int32)
    pair = jnp.stack([2 * bitsum - n_live,
                      jnp.broadcast_to(n_live, bitsum.shape)])
    return pair if acc is None else acc + pair


def vote_decode(pair: jax.Array, agg: str, trim_f: int = 0) -> jax.Array:
    """(2, d) int32 vote pair -> (d,) f32 robust aggregate in [-1, 1].

    Closed forms from ``s = pair[0]`` (signed count) and ``n = pair[1]``
    (live count), with ``c = (s + n) / 2`` the number of +1 votes (always
    integral: s and n have equal parity, preserved by additive folds):

      mean        s / n                      (the plain masked sign mean)
      vote        sign(s)                    (coordinate majority; 0 at tie)
      trimmed(f)  drop the f largest and f smallest votes, average the
                  m = n - 2f survivors. Sorting +/-1 votes puts the -1s
                  first, so the survivors keep plus' = clip(c - f, 0, m)
                  of the +1 votes: (2*plus' - m) / m. When a round is
                  over-trimmed (n <= 2f) the trim level degrades to the
                  deepest possible, f_eff = (n - 1) // 2 — i.e. the median.
      median      trimmed with runtime f = (n - 1) // 2 — for +/-1 votes
                  this equals sign(s) for odd n and the 0-at-tie midpoint
                  rule for even n (identical to vote in value; kept as a
                  separate mode for the standard robust-aggregation name).

    trimmed(0) is EXACTLY the mean. All-dead coordinates (n_live = 0)
    decode to 0 in every mode. Everything here is integer-derived, so the
    result is bit-identical to the dense-matrix oracle
    (tests/test_robust_agg.py).
    """
    if agg not in VOTE_AGG_MODES:
        raise ValueError(f"unknown vote agg mode {agg!r}; expected one of "
                         f"{VOTE_AGG_MODES}")
    s = pair[0].astype(jnp.float32)
    n = pair[1].astype(jnp.float32)
    if agg == "mean":
        return s / jnp.maximum(n, 1.0)
    if agg == "vote":
        return jnp.sign(s)
    f_max = jnp.floor((jnp.maximum(n, 1.0) - 1.0) / 2.0)
    f = f_max if agg == "median" else jnp.minimum(jnp.float32(trim_f), f_max)
    c = (s + n) * 0.5
    m = jnp.maximum(n - 2.0 * f, 1.0)
    plus = jnp.clip(c - f, 0.0, m)
    return jnp.where(n > 0, (2.0 * plus - m) / m, 0.0)


def dense_masked_sum(payload: jax.Array, weights: jax.Array,
                     acc: jax.Array | None = None) -> jax.Array:
    """Server side of the dense fp32 uplink: one weighted einsum.

    (n_clients, d) payload + (n_clients,) weights -> (d,) f32 weighted sum —
    the aggregation every dense-wire codec (identity, qsgd, dp-over-dense)
    shares. Dead clients (weight 0) contribute exactly 0. ``acc`` carries a
    running partial sum across client shards (the streaming driver's dense
    fallback: the carry stays one (d,) buffer).
    """
    out = jnp.einsum("nd,n->d", payload.astype(jnp.float32), weights)
    return out if acc is None else acc + out


def scatter_sum_coo(values: jax.Array, indices: jax.Array,
                    weights: jax.Array, n_coords: int,
                    acc: jax.Array | None = None) -> jax.Array:
    """Server side of the sparse COO uplink: weighted scatter-add.

    (n_clients, k) f32 values + (n_clients, k) int32 indices +
    (n_clients,) f32 weights -> (n_coords,) f32 weighted sum. Dead clients
    (weight 0) contribute exactly 0; duplicate indices across clients
    accumulate. The compressed-domain counterpart of ``unpack_sum`` for the
    "sparse_coo" wire layout — the dense (n_clients, d) scatter surface
    never exists, only the output-sized accumulator. ``acc`` scatter-adds
    into a carried (n_coords,) partial sum instead of a fresh zero buffer
    (streaming cohort fold).
    """
    vals = (values * weights[:, None]).reshape(-1)
    idx = indices.reshape(-1)
    base = jnp.zeros((n_coords,), jnp.float32) if acc is None else acc
    return base.at[idx].add(vals)


def unpack_sum_dense(packed: jax.Array, weights: jax.Array,
                     acc: jax.Array | None = None) -> jax.Array:
    """Legacy dense-matrix weighted sign sum (pre-fused server decode).

    Materializes the full (n_clients, d) fp32 sign matrix before the einsum
    — a 32x working-set blowup over the wire bytes. Kept ONLY as the oracle
    for the sign-reduce equivalence tests and as the "old" side of the
    ``fed_round_step`` benchmark; no production path calls it. ``acc``
    mirrors the fold hook of :func:`unpack_sum` so the oracle covers the
    streaming fold tests too.
    """
    signs = jax.vmap(unpack_signs)(packed).astype(jnp.float32)
    out = jnp.einsum("nd,n->d", signs, weights)
    return out if acc is None else acc + out


def psum_accumulator(acc: jax.Array, axis_name: str) -> jax.Array:
    """Cross-device reduce of a wire ACCUMULATOR over a named mesh axis.

    Every codec's ``aggregate`` is a linear SUM over its client axis,
    so per-device partial accumulators combine by plain addition — one
    ``lax.psum`` of the (d,)-sized (or (d_pad,)-sized) f32 buffer — or, for
    the robust ``agg=vote|trimmed|median`` modes, of the (2, d_pad) int32
    vote pair (:func:`vote_accumulator`) — is the
    entire cross-device protocol of a streamed multi-device round. Per
    device that is O(d) fp32 on the interconnect, independent of cohort
    size: the compressed-domain analogue of the server all-reduce, and the
    ONLY collective the multi-device cohort engine is allowed to emit
    (jaxpr-pinned in tests/test_cohort_stream.py). Integer-valued sign sums
    (0/1 masks) stay exact under the psum's reduction order, which is what
    makes device count a bit-invariant choice there.
    """
    return jax.lax.psum(acc, axis_name)
