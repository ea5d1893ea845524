"""Bit-level primitives: 64-bit keys as int32 pairs, packed global addresses,
unsigned comparisons, and the lock-index hash.

TPUs have no native 64-bit integer lanes, so all 64-bit quantities (keys,
values — reference ``Key``/``Value`` uint64) travel as (hi, lo) pairs of
int32 words holding the uint32 bit patterns.  Comparisons flip the sign bit
to reuse signed int32 compares as unsigned ones.

Global addresses are packed int32 {node:8, page:24} — the TPU analogue of the
reference's 64-bit ``GlobalAddress`` {nodeID:16, offset:48}
(``GlobalAddress.h:10-16``); word-granular sub-addressing uses a separate
word-offset field instead of byte offsets.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from sherman_tpu.config import ADDR_PAGE_BITS, ADDR_PAGE_MASK

_SIGN = np.int32(np.uint32(0x80000000).view(np.int32))
_U32_MASK = (1 << 32) - 1


# -- host-side scalar helpers -------------------------------------------------

def key_to_pair(k: int) -> tuple[int, int]:
    """Split a Python uint64 key into (hi, lo) int32 bit patterns."""
    k = int(k) & ((1 << 64) - 1)
    hi = np.uint32(k >> 32).view(np.int32).item()
    lo = np.uint32(k & _U32_MASK).view(np.int32).item()
    return hi, lo


def pair_to_key(hi, lo) -> int:
    """Rebuild the Python uint64 key from (hi, lo) int32 bit patterns."""
    hi_u = int(np.int64(int(hi)) & _U32_MASK)
    lo_u = int(np.int64(int(lo)) & _U32_MASK)
    return (hi_u << 32) | lo_u


def keys_to_pairs(ks) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized host conversion: uint64 array -> (hi, lo) int32 arrays."""
    ks = np.asarray(ks, dtype=np.uint64)
    hi = (ks >> np.uint64(32)).astype(np.uint32).view(np.int32)
    lo = (ks & np.uint64(_U32_MASK)).astype(np.uint32).view(np.int32)
    return hi, lo


def pairs_to_keys(hi, lo) -> np.ndarray:
    hi = np.asarray(hi).view(np.uint32).astype(np.uint64)
    lo = np.asarray(lo).view(np.uint32).astype(np.uint64)
    return (hi << np.uint64(32)) | lo


# -- device-side (jnp) unsigned compare on (hi, lo) pairs ---------------------

def _ux(x):
    return jnp.bitwise_xor(x, _SIGN)


def u32_lt(a, b):
    return _ux(a) < _ux(b)


def u32_le(a, b):
    return _ux(a) <= _ux(b)


def key_lt(ahi, alo, bhi, blo):
    """(ahi,alo) < (bhi,blo) as uint64."""
    return u32_lt(ahi, bhi) | ((ahi == bhi) & u32_lt(alo, blo))


def key_le(ahi, alo, bhi, blo):
    return u32_lt(ahi, bhi) | ((ahi == bhi) & u32_le(alo, blo))


def key_eq(ahi, alo, bhi, blo):
    return (ahi == bhi) & (alo == blo)


# -- packed global page addresses --------------------------------------------

def make_addr(node, page):
    """Pack (node, page) into an int32 address; works for ints and arrays."""
    if isinstance(node, (int, np.integer)) and isinstance(page, (int, np.integer)):
        v = (int(node) << ADDR_PAGE_BITS) | (int(page) & ADDR_PAGE_MASK)
        return np.uint32(v).view(np.int32).item()
    return jnp.bitwise_or(
        jnp.left_shift(jnp.asarray(node, jnp.int32), ADDR_PAGE_BITS),
        jnp.bitwise_and(jnp.asarray(page, jnp.int32), ADDR_PAGE_MASK),
    )


def addr_node(addr):
    if isinstance(addr, (int, np.integer)):
        return (int(np.int64(int(addr)) & _U32_MASK)) >> ADDR_PAGE_BITS
    a = jnp.asarray(addr, jnp.int32).astype(jnp.uint32)
    return jnp.right_shift(a, ADDR_PAGE_BITS).astype(jnp.int32)


def addr_page(addr):
    if isinstance(addr, (int, np.integer)):
        return int(addr) & ADDR_PAGE_MASK
    return jnp.bitwise_and(jnp.asarray(addr, jnp.int32), ADDR_PAGE_MASK)


NULL_ADDR = 0


def addr_is_null(addr):
    if isinstance(addr, (int, np.integer)):
        return int(addr) == 0
    return addr == 0


# -- lock leases --------------------------------------------------------------
# A held global lock word encodes WHO holds it and under which lease
# epoch: {epoch:15, owner:16} (bit 31 stays clear so the int32 word is
# non-negative and mask arithmetic never sees the sign bit).  0 = free.
# The owner field is the client tag (client_id + 1, nonzero); the epoch
# is the owner's lease generation in the cluster's epoch table
# (``Cluster.lease_is_live``).  A holder whose (owner, epoch) no longer
# matches the table is DEAD — its lock is revocable by masked CAS on
# exactly these fields (the FUSEE-style lock-lease recovery shape).
# Step atomicity makes revocation sound: a dead client's protected
# write either landed as one step or not at all, so freeing its lock
# can never expose a torn page.

LEASE_OWNER_BITS = 16
LEASE_EPOCH_BITS = 15
LEASE_OWNER_MASK = (1 << LEASE_OWNER_BITS) - 1
LEASE_EPOCH_MASK = (1 << LEASE_EPOCH_BITS) - 1
# both fields — the bits a lease revocation masked-CAS compares/swaps
LEASE_MASK = (LEASE_EPOCH_MASK << LEASE_OWNER_BITS) | LEASE_OWNER_MASK


def lease_word(owner_tag: int, epoch: int = 1) -> int:
    """Pack (owner tag, lease epoch) into a held-lock word (int32 >= 0)."""
    assert 0 < int(owner_tag) <= LEASE_OWNER_MASK, "owner tag out of range"
    return ((int(epoch) & LEASE_EPOCH_MASK) << LEASE_OWNER_BITS) \
        | (int(owner_tag) & LEASE_OWNER_MASK)


def lease_owner(word: int) -> int:
    """Owner tag of a held-lock word (0 = free)."""
    return int(np.int64(int(word)) & _U32_MASK) & LEASE_OWNER_MASK


def lease_epoch(word: int) -> int:
    """Lease epoch of a held-lock word."""
    return (int(np.int64(int(word)) & _U32_MASK)
            >> LEASE_OWNER_BITS) & LEASE_EPOCH_MASK


# -- lock hash ---------------------------------------------------------------
# The reference hashes page addresses onto the on-chip lock table with
# CityHash64 % kNumOfLock (Tree.cpp:702-707,832-842).  We use a 32-bit
# Murmur3 finalizer — cheap on the VPU and well-mixing for packed addresses.

def hash32(x):
    x = jnp.asarray(x, jnp.int32).astype(jnp.uint32)
    x = jnp.bitwise_xor(x, jnp.right_shift(x, 16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = jnp.bitwise_xor(x, jnp.right_shift(x, 13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = jnp.bitwise_xor(x, jnp.right_shift(x, 16))
    return x


def lock_index(addr, locks_per_node: int):
    """Lock word index for a page address (on the page's owner node)."""
    return (hash32(addr) % jnp.uint32(locks_per_node)).astype(jnp.int32)


def hash32_np(x: np.ndarray) -> np.ndarray:
    """Vectorized host twin of :func:`hash32` on uint32 arrays —
    bit-exact, no device.  (Third sibling beside the device and scalar
    forms so a constant tweak can never diverge them: the leaf cache's
    host-side table placement must agree with its device probe.)"""
    v = np.asarray(x).astype(np.uint32).copy()
    v ^= v >> np.uint32(16)
    v *= np.uint32(0x85EBCA6B)
    v ^= v >> np.uint32(13)
    v *= np.uint32(0xC2B2AE35)
    v ^= v >> np.uint32(16)
    return v


def hash32_host(x: int) -> int:
    """Host scalar twin of :func:`hash32` — bit-exact, pure Python.  The
    host lock path hashes one address per lock acquisition; routing that
    through the jnp version dispatches a device computation per call."""
    v = int(x) & _U32_MASK
    v ^= v >> 16
    v = (v * 0x85EBCA6B) & _U32_MASK
    v ^= v >> 13
    v = (v * 0xC2B2AE35) & _U32_MASK
    v ^= v >> 16
    return v


def lock_index_host(addr: int, locks_per_node: int) -> int:
    """Host scalar twin of :func:`lock_index` (same word, no device)."""
    return hash32_host(addr) % locks_per_node


# -- device-side 64-bit pair arithmetic ---------------------------------------
# TPUs have no 64-bit integer lanes; these compose uint32 (hi, lo) pairs
# into the few u64 ops the device-resident workload generator needs
# (full-width multiply for the splitmix64 finalizer).  All inputs/outputs
# are jnp.uint32 arrays; shifts are Python-int static.

def u32_mul_full(a, b):
    """Full 32x32 -> 64 multiply via 16-bit limbs: returns (hi, lo)
    uint32.  jnp uint32 * uint32 keeps only the low word, so the high
    word is assembled from the four partial products (each exact: a
    16x16 product fits 32 bits)."""
    a0, a1 = a & jnp.uint32(0xFFFF), a >> 16
    b0, b1 = b & jnp.uint32(0xFFFF), b >> 16
    p00, p01 = a0 * b0, a0 * b1
    p10, p11 = a1 * b0, a1 * b1
    t = (p00 >> 16) + (p01 & jnp.uint32(0xFFFF)) + (p10 & jnp.uint32(0xFFFF))
    lo = (p00 & jnp.uint32(0xFFFF)) | ((t & jnp.uint32(0xFFFF)) << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (t >> 16)
    return hi, lo


def u64_mul(ahi, alo, bhi, blo):
    """(ahi, alo) * (bhi, blo) mod 2^64 -> (hi, lo) uint32 pairs.  The
    cross terms contribute only to the high word (their low halves are
    shifted out), so wrapping uint32 multiplies suffice there."""
    hi, lo = u32_mul_full(alo, blo)
    hi = hi + alo * bhi + ahi * blo
    return hi, lo


def u64_shr(hi, lo, s: int):
    """Logical right shift of a (hi, lo) uint32 pair by static s."""
    if s == 0:
        return hi, lo
    if s < 32:
        return hi >> s, (lo >> s) | (hi << (32 - s))
    if s == 32:
        return jnp.zeros_like(hi), hi
    return jnp.zeros_like(hi), hi >> (s - 32)


def u64_shr_dyn(hi, lo, s):
    """Logical right shift of a (hi, lo) uint32 pair by a TRACED shift
    ``s`` (uint32 scalar or array, 0 <= s <= 63).  The static
    :func:`u64_shr` branches in Python; the device router probe needs
    the shift as data (the span grows under serving and a static shift
    would retrace the sealed prep program).  Shift amounts are clamped
    before use — XLA shifts >= bit width are undefined, so each branch
    only ever sees an in-range amount and ``jnp.where`` selects."""
    s = jnp.asarray(s, jnp.uint32)
    s_lo = jnp.minimum(s, jnp.uint32(31))          # safe for the s<32 lanes
    s_hi = jnp.where(s >= jnp.uint32(32), s - jnp.uint32(32), jnp.uint32(0))
    lo_small = (lo >> s_lo) | jnp.where(
        s_lo > 0, hi << (jnp.uint32(32) - s_lo), jnp.uint32(0))
    hi_small = hi >> s_lo
    lo_big = hi >> s_hi
    big = s >= jnp.uint32(32)
    out_hi = jnp.where(big, jnp.uint32(0), hi_small)
    out_lo = jnp.where(big, lo_big, jnp.where(s == 0, lo, lo_small))
    return out_hi, out_lo


_MIX64_C1 = (0xBF58476D, 0x1CE4E5B9)  # splitmix64 finalizer constants
_MIX64_C2 = (0x94D049BB, 0x133111EB)


def mix64_pair(hi, lo):
    """splitmix64 finalizer on (hi, lo) uint32 pairs — bit-exact twin of
    the native prep's rank->key map (native/src/prep.cc mix64), so a
    device-generated batch hits exactly the keys the bulk load wrote."""
    h, l = u64_shr(hi, lo, 30)
    hi, lo = hi ^ h, lo ^ l
    hi, lo = u64_mul(hi, lo, jnp.uint32(_MIX64_C1[0]), jnp.uint32(_MIX64_C1[1]))
    h, l = u64_shr(hi, lo, 27)
    hi, lo = hi ^ h, lo ^ l
    hi, lo = u64_mul(hi, lo, jnp.uint32(_MIX64_C2[0]), jnp.uint32(_MIX64_C2[1]))
    h, l = u64_shr(hi, lo, 31)
    return hi ^ h, lo ^ l


def mix64_np(x: np.ndarray) -> np.ndarray:
    """Vectorized host twin of :func:`mix64_pair` on uint64 arrays
    (numpy integer overflow wraps, matching the native mix64)."""
    x = np.asarray(x, np.uint64).copy()
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def mix64_host(x: int) -> int:
    """Host scalar twin of :func:`mix64_pair` (and of the native
    mix64) — for tests and native-free key-map parity."""
    x = int(x) & ((1 << 64) - 1)
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & ((1 << 64) - 1)
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & ((1 << 64) - 1)
    x ^= x >> 31
    return x
