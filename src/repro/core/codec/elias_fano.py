"""Elias-Fano encoding of monotone integer sequences (paper §3.2).

Two-level representation: the low ``l = floor(log2(U/n))`` bits of each value
are stored at fixed width; the high bits are stored as a unary-coded bitmap
(bit ``high[i] + i`` set). Worst-case size for n values over universe U is
``2n + n*ceil(log2(U/n))`` bits — the bound the paper uses both for its sparse
in-memory index sizing and for fixed-size LRU cache entries (§3.3, §3.4).

Host encode/decode are numpy; :func:`decode_slot_jnp` is the pure-jnp decoder
for the fixed-size *slot* format used by the device-resident graph (see
``core/storage/index_store.py``): fixed slots let the device address any
adjacency list directly by vertex ID — the TPU analogue of the paper's
fixed-entry LRU cache.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax.numpy as jnp

from .bitpack import pack_fixed, unpack_fixed_np, unpack_fixed_jnp, words_for_bits

WORD_BITS = 32


def low_bits_width(n: int, universe: int) -> int:
    """l = max(0, ceil(log2(U/n))).

    The ceil split keeps the high bitmap within ``2n + 1`` bits, matching the
    paper's worst-case form ``2R + R*ceil(log2(N/R))`` exactly (§3.3)."""
    if n <= 0:
        return 0
    return max(0, int(math.ceil(math.log2(max(1, universe) / n))))


def worst_case_bits(n: int, universe: int) -> int:
    """Paper bound: 2n + n*ceil(log2(U/n)) bits (§3.3)."""
    if n <= 0:
        return 0
    return 2 * n + n * int(math.ceil(math.log2(max(2, universe) / n)))


def worst_case_record_bytes(n: int, universe: int) -> int:
    """The §3.4 fixed-entry cache bound in bytes — the ONE definition of
    the EF entry sizing rule (index store, serving-tier modeled LRUs, and
    the codec registry all derive from here)."""
    return (worst_case_bits(n, universe) + 7) // 8


@dataclass(frozen=True)
class EFList:
    """A variable-size Elias-Fano encoded monotone list."""
    n: int
    universe: int
    low_width: int
    low_words: np.ndarray    # uint32
    high_words: np.ndarray   # uint32 unary bitmap, n + (max_high) + 1 bits

    @property
    def size_bits(self) -> int:
        return 32 * (len(self.low_words) + len(self.high_words))


def encode(values: np.ndarray, universe: int,
           low_width: int | None = None) -> EFList:
    """Encode; ``low_width`` overrides the canonical split (the record
    header stores the width per record, so any 0..32 split decodes)."""
    values = np.asarray(values, dtype=np.uint64)
    n = len(values)
    if n and (np.any(np.diff(values.astype(np.int64)) < 0)):
        raise ValueError("Elias-Fano requires a non-decreasing sequence")
    if n and int(values[-1]) >= universe:
        raise ValueError("value out of universe")
    l = low_bits_width(n, universe) if low_width is None else int(low_width)
    if not 0 <= l <= 32:
        raise ValueError(f"low_width {l} outside [0, 32]")
    low = values & np.uint64((1 << l) - 1) if l else np.zeros(n, np.uint64)
    high = (values >> np.uint64(l)).astype(np.int64)
    low_words = pack_fixed(low, l) if l else np.zeros(0, np.uint32)
    hb_bits = n + (int(high[-1]) if n else 0) + 1
    high_words = np.zeros(words_for_bits(hb_bits), dtype=np.uint32)
    if n:
        pos = high + np.arange(n, dtype=np.int64)
        np.bitwise_or.at(high_words, pos // WORD_BITS,
                         (np.uint32(1) << (pos % WORD_BITS).astype(np.uint32)))
    return EFList(n=n, universe=universe, low_width=l,
                  low_words=low_words, high_words=high_words)


def decode(ef: EFList) -> np.ndarray:
    if ef.n == 0:
        return np.zeros(0, dtype=np.uint64)
    bits = np.unpackbits(ef.high_words.view(np.uint8), bitorder="little")
    pos = np.flatnonzero(bits)[: ef.n].astype(np.int64)
    high = (pos - np.arange(ef.n)).astype(np.uint64)
    low = unpack_fixed_np(ef.low_words, ef.n, ef.low_width)
    return (high << np.uint64(ef.low_width)) | low


# ---------------------------------------------------------------------------
# Compact byte-record format (block-based on-disk index store, §3.3)
# ---------------------------------------------------------------------------
# Record: u8 count | u8 low_width | low bytes (ceil(count*lw/8)) | high bytes.
# Trailing zero bits of the high bitmap are trimmed (decode re-pads), so the
# record size tracks the true encoded size, not word-rounded slack. The
# low/high split is chosen PER RECORD: the header already carries the width,
# so instead of the canonical ``ceil(log2(U/n))`` (a universe-level rule that
# assumes uniform gaps) each record takes the width minimizing its own byte
# count. After a locality reorder the per-list spans collapse far below the
# universe, and the per-record optimum tracks the span — this is where the
# relabeling actually turns into adjacency-tier bytes.


def record_bytes_for_width(n: int, last: int, low_width: int) -> int:
    """Exact record size (header + low + high) for an n-list whose maximum
    value is ``last`` under a given split. The high bitmap needs exactly
    ``n + (last >> low_width)`` bits: the final set bit sits at position
    ``(n - 1) + (last >> low_width)``."""
    if n == 0:
        return 2
    return (2 + (n * low_width + 7) // 8
            + (n + (last >> low_width) + 7) // 8)


def optimal_low_width(n: int, last: int, universe: int) -> int:
    """Smallest-record split for one list (ties -> smaller width)."""
    hi = max(1, min(32, int(max(universe - 1, 1)).bit_length()))
    return min(range(hi + 1),
               key=lambda lw: (record_bytes_for_width(n, last, lw), lw))


def encode_record(values: np.ndarray, universe: int) -> np.ndarray:
    values = np.asarray(values, dtype=np.uint64)
    n = len(values)
    if n > 255:
        raise ValueError("record format supports <= 255 neighbors")
    if n == 0:
        return np.asarray([0, 0], dtype=np.uint8)
    last = int(values[-1])
    lw = optimal_low_width(n, last, universe)
    e = encode(values, universe, low_width=lw)
    low_bytes = e.low_words.view(np.uint8)[: (n * lw + 7) // 8]
    hb_bits = n + (last >> lw)
    high_bytes = e.high_words.view(np.uint8)[: (hb_bits + 7) // 8]
    return np.concatenate([
        np.asarray([n, lw], dtype=np.uint8), low_bytes, high_bytes])


def decode_record(rec: np.ndarray, universe: int) -> np.ndarray:
    rec = np.asarray(rec, dtype=np.uint8)
    n, lw = int(rec[0]), int(rec[1])
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    nlb = (n * lw + 7) // 8
    low_b = rec[2:2 + nlb]
    high_b = rec[2 + nlb:]
    def _pad_words(b):
        pad = (-len(b)) % 4
        if pad:
            b = np.concatenate([b, np.zeros(pad, np.uint8)])
        return b.copy().view(np.uint32)
    ef = EFList(n=n, universe=universe, low_width=lw,
                low_words=_pad_words(low_b), high_words=_pad_words(high_b))
    return decode(ef)


# ---------------------------------------------------------------------------
# Fixed-size slot format (device-resident graph / LRU cache entries)
# ---------------------------------------------------------------------------
# Slot layout, uint32 words:
#   word 0            : n (actual neighbor count, <= r_max)
#   words [1 .. LW]   : packed low bits (r_max * l bits, fixed l from r_max/U)
#   words [LW+1 .. ]  : high bitmap (2*r_max + 1 bits worst case)
# Unused trailing entries encode value `universe-1` padding removed on decode.


def slot_layout(r_max: int, universe: int) -> tuple[int, int, int, int]:
    """Returns (low_width, low_words, high_words, slot_words)."""
    l = low_bits_width(r_max, universe)
    lw = words_for_bits(r_max * l)
    # high bitmap: r_max set bits, max high value (universe-1)>>l < 2*r_max + 1
    hb = words_for_bits(r_max + ((universe - 1) >> l) + 1)
    return l, lw, hb, 1 + lw + hb


def encode_slot(values: np.ndarray, r_max: int, universe: int) -> np.ndarray:
    """Encode an ascending list (len <= r_max) into a fixed-size uint32 slot.

    The list is padded to r_max with ``universe - 1`` sentinels so the slot
    shape is static — decode recovers the true length from word 0.
    """
    values = np.asarray(values, dtype=np.uint64)
    n = len(values)
    if n > r_max:
        raise ValueError(f"{n} > r_max {r_max}")
    l, lw, hb, total = slot_layout(r_max, universe)
    padded = np.concatenate([values,
                             np.full(r_max - n, universe - 1, dtype=np.uint64)])
    slot = np.zeros(total, dtype=np.uint32)
    slot[0] = n
    low = padded & np.uint64((1 << l) - 1) if l else np.zeros(r_max, np.uint64)
    if l:
        slot[1:1 + lw] = pack_fixed(low, l, out=np.zeros(lw, np.uint32))
    high = (padded >> np.uint64(l)).astype(np.int64)
    pos = high + np.arange(r_max, dtype=np.int64)
    hw = np.zeros(hb, dtype=np.uint32)
    np.bitwise_or.at(hw, pos // WORD_BITS,
                     (np.uint32(1) << (pos % WORD_BITS).astype(np.uint32)))
    slot[1 + lw:] = hw
    return slot


def encode_slots(values: np.ndarray, counts: np.ndarray, r_max: int,
                 universe: int) -> np.ndarray:
    """:func:`encode_slot` of many lists at once: row i holds its list's
    ``counts[i]`` values ascending in ``values[i, :counts[i]]`` (the rest is
    ignored) -> [n, slot_words] uint32, each row equal to ``encode_slot`` of
    that list. Rows go 1,024 at a time, so that a block's columns stay in
    cache."""
    values = np.asarray(values)[:, :r_max]
    counts = np.asarray(counts, dtype=np.int64)
    n = len(values)
    if n and int(counts.max()) > r_max:
        raise ValueError(f"{int(counts.max())} > r_max {r_max}")
    l, lw, hb, total = slot_layout(r_max, universe)
    cols = np.arange(r_max)
    out = np.zeros((n, total + 1), dtype=np.uint64)  # uint32 words + spill
    out[:, 0] = counts
    chunk = 1024
    for s in range(0, n, chunk):
        v = np.where(cols < counts[s:s + chunk, None], values[s:s + chunk],
                     universe - 1).astype(np.uint64)
        m = len(v)
        if l:
            # Value i's l low bits start at bit i*l of the low words, the
            # same place in every row: a column at a time, two words each.
            low = v & np.uint64((1 << l) - 1)
            words = out[s:s + m, 1:]
            for i in range(r_max):
                w, off = divmod(i * l, WORD_BITS)
                part = low[:, i] << np.uint64(off)
                words[:, w] |= part & np.uint64(0xFFFFFFFF)
                words[:, w + 1] |= part >> np.uint64(WORD_BITS)
        # High parts: bit (v >> l) + i of the bitmap. The bits of a row
        # are distinct, so each word is the sum of its bits.
        pos = (v >> np.uint64(l)).astype(np.int64) + cols
        word = np.arange(m)[:, None] * hb + pos // WORD_BITS
        out[s:s + m, 1 + lw:total] = np.bincount(
            word.ravel(), np.left_shift(1, pos % WORD_BITS).ravel(),
            minlength=m * hb).reshape(m, hb)
    return out[:, :total].astype(np.uint32)


def decode_slot_np(slot: np.ndarray, r_max: int, universe: int) -> np.ndarray:
    l, lw, hb, _ = slot_layout(r_max, universe)
    n = int(slot[0])
    bits = np.unpackbits(slot[1 + lw:].view(np.uint8), bitorder="little")
    pos = np.flatnonzero(bits)[:r_max].astype(np.int64)
    high = (pos - np.arange(r_max)).astype(np.uint64)
    low = unpack_fixed_np(slot[1:1 + lw], r_max, l)
    return ((high << np.uint64(l)) | low)[:n]


def decode_slot_jnp(slot: jnp.ndarray, r_max: int, universe: int):
    """Pure-jnp decode of one slot -> (neighbors[r_max] int32, count int32).

    Padding entries decode to ``universe - 1``; callers mask with ``count``.
    The select-in-bitmap uses a cumulative-sum rank: position of the i-th set
    bit is ``argmax(cumsum(bits) == i+1)`` — O(r_max * bitmap_bits) compares,
    VREG-friendly for the bounded bitmaps the paper's worst case guarantees.
    """
    l, lw, hb, _ = slot_layout(r_max, universe)
    n = slot[0].astype(jnp.int32)
    hw = slot[1 + lw:].astype(jnp.uint32)
    nbits = hb * WORD_BITS
    bitidx = jnp.arange(nbits, dtype=jnp.uint32)
    bits = (hw[bitidx // WORD_BITS] >> (bitidx % WORD_BITS)) & jnp.uint32(1)
    csum = jnp.cumsum(bits.astype(jnp.int32))
    ranks = jnp.arange(1, r_max + 1, dtype=jnp.int32)
    # pos[i] = first index where csum == i+1 (and bit set there).
    hit = (csum[None, :] == ranks[:, None])
    pos = jnp.argmax(hit, axis=1).astype(jnp.int32)
    high = pos - jnp.arange(r_max, dtype=jnp.int32)
    low = unpack_fixed_jnp(slot[1:1 + lw], r_max, l).astype(jnp.int32)
    vals = jnp.left_shift(high, l) | low
    return vals, n
