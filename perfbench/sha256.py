"""SHA-256 from FIPS 180-4 in plain Python, with an optional op counter.

The benchmark's own copy: the reference that decides ``correct`` and the
roofline's op count must not lean on the program under test.  Used for
the midstate of the device reference scan (the first 64 header bytes do
not depend on the nonce) and, with ``Ops``, to prove the spec op count of
``opcount.py`` by counting the operations of a real digest.
"""

from __future__ import annotations

import struct

M32 = 0xFFFFFFFF

# fmt: off
K = (
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
)
IV = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)
# fmt: on


class Ops:
    """32-bit word operations, each counted as one (a rotate too)."""

    def __init__(self) -> None:
        self.n = 0

    def add(self, x, y):
        self.n += 1
        return (x + y) & M32

    def xor(self, x, y):
        self.n += 1
        return x ^ y

    def and_(self, x, y):
        self.n += 1
        return x & y

    def not_(self, x):
        self.n += 1
        return ~x & M32

    def rotr(self, x, k):
        self.n += 1
        return ((x >> k) | (x << (32 - k))) & M32

    def shr(self, x, k):
        self.n += 1
        return x >> k

    def lt(self, x, y):
        self.n += 1
        return int(x < y)

    def eq(self, x, y):
        self.n += 1
        return int(x == y)

    def or_(self, x, y):
        self.n += 1
        return x | y


def compress(state, chunk: bytes, ops: Ops | None = None) -> tuple[int, ...]:
    """FIPS 180-4 section 6.2.2 over one 64-byte chunk."""
    o = ops or Ops()
    w = list(struct.unpack(">16I", chunk))
    for t in range(16, 64):
        x, y = w[t - 15], w[t - 2]
        s0 = o.xor(o.xor(o.rotr(x, 7), o.rotr(x, 18)), o.shr(x, 3))
        s1 = o.xor(o.xor(o.rotr(y, 17), o.rotr(y, 19)), o.shr(y, 10))
        w.append(o.add(o.add(o.add(s1, w[t - 7]), s0), w[t - 16]))
    a, b, c, d, e, f, g, h = state
    for t in range(64):
        big1 = o.xor(o.xor(o.rotr(e, 6), o.rotr(e, 11)), o.rotr(e, 25))
        ch = o.xor(o.and_(e, f), o.and_(o.not_(e), g))
        t1 = o.add(o.add(o.add(o.add(h, big1), ch), K[t]), w[t])
        big0 = o.xor(o.xor(o.rotr(a, 2), o.rotr(a, 13)), o.rotr(a, 22))
        maj = o.xor(o.xor(o.and_(a, b), o.and_(a, c)), o.and_(b, c))
        t2 = o.add(big0, maj)
        h, g, f, e, d, c, b, a = g, f, e, o.add(d, t1), c, b, a, o.add(t1, t2)
    return tuple(o.add(s, v) for s, v in zip(state, (a, b, c, d, e, f, g, h)))


def pad(message: bytes) -> bytes:
    bits = len(message) * 8
    tail = b"\x80" + b"\x00" * ((55 - len(message)) % 64)
    return message + tail + struct.pack(">Q", bits)


def midstate(header76: bytes) -> tuple[int, ...]:
    """The state after the header's first 64 bytes."""
    return compress(IV, header76[:64])


def below(digest_words, target_words, ops: Ops | None = None) -> bool:
    """``digest < target`` as 256-bit big-endian integers, limb by limb:
    ``lt0 | (eq0 & (lt1 | (eq1 & ... lt7)))``."""
    o = ops or Ops()
    acc = o.lt(digest_words[7], target_words[7])
    for i in range(6, -1, -1):
        acc = o.or_(o.lt(digest_words[i], target_words[i]),
                    o.and_(o.eq(digest_words[i], target_words[i]), acc))
    return bool(acc)


def candidate(header76: bytes, nonce: int, target_words, ops: Ops | None = None):
    """One mining candidate as the spec counts it: the second chunk from
    the midstate, the second SHA-256 over the digest, and the target
    compare.  Returns (digest bytes, meets target)."""
    o = ops or Ops()
    mid = midstate(header76)  # precomputed once per template, not counted
    block2 = pad(header76 + struct.pack(">I", nonce))[64:]
    first = compress(mid, block2, o)
    second = compress(IV, pad(struct.pack(">8I", *first)), o)
    return struct.pack(">8I", *second), below(second, target_words, o)


def target_words(difficulty: int) -> tuple[int, ...]:
    """The target ``2**(256 - d)`` (hash must be below it) as 8 words;
    ``d = 0`` clamps to all ones."""
    t = min(1 << (256 - difficulty), (1 << 256) - 1)
    return tuple((t >> (32 * (7 - i))) & M32 for i in range(8))


def header_check(header80: bytes, prev_words, target_words, ops: Ops | None = None) -> bool:
    """One header of a chain as the spec counts it: SHA-256d of its 80
    bytes (three compressions), the target compare, and its previous
    hash against the parent's digest ``prev_words``, word by word."""
    o = ops or Ops()
    block = pad(header80)
    first = compress(compress(IV, block[:64], o), block[64:], o)
    second = compress(IV, pad(struct.pack(">8I", *first)), o)
    ok = below(second, target_words, o)
    claimed = struct.unpack(">8I", header80[4:36])
    link = o.eq(claimed[0], prev_words[0])
    for c, p in zip(claimed[1:], prev_words[1:]):
        link = o.and_(link, o.eq(c, p))
    return ok and bool(link)
