"""The plain reference that decides ``correct``.

It imports nothing of the program.  A header is the 80 bytes
``>I32s32sIII`` (version, previous hash, merkle root, timestamp,
difficulty, nonce); its proof of work is SHA-256d of those bytes, read
as a big-endian integer, below ``2**(256 - difficulty)``.

- ``pow_ok``: every sealed header, with ``hashlib``.
- ``chain_first_invalid``: the first header of a packed fixed-difficulty
  chain that breaks a rule (genesis identity, the declared difficulty,
  the link to its parent's SHA-256d, proof of work from header 1 on), by
  a sequential ``hashlib`` loop over the bytes.
- ``FirstHitScan``: whether a sealed nonce is the first hit in canonical
  order, i.e. no nonce in ``[0, nonce)`` of the same header meets the
  target.  At d28 that is ~2**28 digests a block, out of reach of
  ``hashlib`` in a run's time, so the scan runs on the device in plain
  ``jax.numpy`` (no kernel, nothing of the program), after the window has
  closed and the node has stopped.  It scans ``[0, nonce]`` and must find
  exactly ``nonce``: a scan that missed the sealed hit itself would be a
  broken reference, and reads as a failure too.
"""

from __future__ import annotations

import functools
import hashlib
import struct

from perfbench import sha256

HEADER = struct.Struct(">I32s32sIII")
#: Genesis of a fixed-difficulty chain: version 1, zero previous hash and
#: merkle root, 2025-01-01T00:00:00Z, nonce 0 (the program's fixed genesis,
#: restated here).
GENESIS_VERSION = 1
GENESIS_TIMESTAMP = 1735689600


def header_bytes(version, prev_hash, merkle_root, timestamp, difficulty, nonce) -> bytes:
    return HEADER.pack(version, prev_hash, merkle_root, timestamp, difficulty, nonce)


def sha256d(data: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


def pow_ok(raw: bytes, difficulty: int) -> bool:
    return int.from_bytes(sha256d(raw), "big") < (1 << (256 - difficulty))


def genesis(difficulty: int) -> bytes:
    return header_bytes(GENESIS_VERSION, bytes(32), bytes(32), GENESIS_TIMESTAMP, difficulty, 0)


def chain_first_invalid(raw: bytes, difficulty: int, start: int = 0) -> int | None:
    """The index of the first header of the packed chain ``raw`` (80 bytes
    each) that breaks a rule, or None where every header keeps them.
    Header 0 must be the genesis of a chain at ``difficulty``, byte for
    byte; every later header declares ``difficulty``, names its parent's
    SHA-256d as its previous hash and meets its target.  With ``start``,
    the headers before it are taken as checked: a chain that differs from
    a checked one only from ``start`` on is checked from there."""
    size = HEADER.size
    n = len(raw) // size
    data = memoryview(raw)
    if start == 0:
        if n and bytes(data[:size]) != genesis(difficulty):
            return 0
        start = 1
    if start >= n:
        return None
    target = 1 << (256 - difficulty)
    want = difficulty.to_bytes(4, "big")
    prev = sha256d(data[(start - 1) * size:start * size])
    for i in range(start, n):
        h = data[i * size:(i + 1) * size]
        digest = sha256d(h)
        if (h[4:36] != prev or h[72:76] != want
                or int.from_bytes(digest, "big") >= target):
            return i
        prev = digest
    return None


@functools.cache
def _scan_fn(batch: int, unroll: int, devices: tuple):
    """One program over ``devices``: device ``i`` scans ``batch`` nonces
    from ``base + i * batch`` and returns the offset of its first hit, or
    ``batch``.  One compile serves every device."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    u32 = jnp.uint32
    k = jnp.asarray(sha256.K, dtype=u32)
    iv = tuple(u32(x) for x in sha256.IV)

    def rotr(x, n):
        return (x >> u32(n)) | (x << u32(32 - n))

    def compress(state, w16):
        def round_(t, carry):
            (a, b, c, d, e, f, g, h), w = carry[:8], carry[8:]
            big1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + big1 + ch + k[t] + w[0]
            big0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22)
            maj = (a & b) ^ (a & c) ^ (b & c)
            s0 = rotr(w[1], 7) ^ rotr(w[1], 18) ^ (w[1] >> u32(3))
            s1 = rotr(w[14], 17) ^ rotr(w[14], 19) ^ (w[14] >> u32(10))
            w_next = w[0] + s0 + w[9] + s1  # schedule word t + 16
            return (t1 + big0 + maj, a, b, c, d + t1, e, f, g) + w[1:] + (w_next,)

        out = lax.fori_loop(0, 64, round_, tuple(state) + tuple(w16), unroll=unroll)
        return tuple(s + v for s, v in zip(state, out[:8]))

    def scan(mid, tail, target, base):
        idx = lax.iota(u32, batch)
        zero = jnp.zeros((batch,), u32)

        def full(x):
            return zero + x

        w1 = (full(tail[0]), full(tail[1]), full(tail[2]), base + idx,
              full(u32(0x80000000))) + (zero,) * 10 + (full(u32(640)),)
        first = compress(tuple(full(m) for m in mid), w1)
        w2 = first + (full(u32(0x80000000)),) + (zero,) * 6 + (full(u32(256)),)
        digest = compress(tuple(full(x) for x in iv), w2)
        # digest < target, most significant word first.
        lt = digest[7] < target[7]
        for i in range(6, -1, -1):
            lt = (digest[i] < target[i]) | ((digest[i] == target[i]) & lt)
        return jnp.min(jnp.where(lt, idx, u32(batch)))

    mesh = Mesh(list(devices), ("d",))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(),) * 4, out_specs=P("d"),
                       check_vma=False)
    def step(mid, tail, target, base):
        # Each device scans its own contiguous block of the step as one
        # flat (batch,) array, the layout the VPU tiles without padding.
        mine = base + lax.axis_index("d").astype(u32) * u32(batch)
        return jnp.reshape(scan(mid, tail, target, mine), (1,))

    return jax.jit(step), NamedSharding(mesh, P())


class FirstHitScan:
    """Finds the first nonce in ``[0, stop)`` of a header that meets its
    target, ``batch`` nonces a device a step, on every device at once,
    with one step in flight while the last is read back."""

    def __init__(self, devices, batch: int, unroll: int):
        self.n = len(devices)
        self.batch = batch
        self.fn, self.one = _scan_fn(batch, unroll, tuple(devices))

    def first_hit(self, header76: bytes, difficulty: int, stop: int) -> int | None:
        import jax
        import numpy as np

        consts = jax.device_put((
            np.asarray(sha256.midstate(header76), np.uint32),
            np.asarray(struct.unpack(">3I", header76[64:76]), np.uint32),
            np.asarray(sha256.target_words(difficulty), np.uint32),
        ), self.one)
        def launch(base):
            return self.fn(*consts, jax.device_put(np.uint32(base % (1 << 32)), self.one))

        bases = range(0, stop, self.batch * self.n)
        inflight = launch(bases[0]) if bases else None
        for k, base in enumerate(bases):
            out = inflight
            inflight = launch(bases[k + 1]) if k + 1 < len(bases) else None
            for i, off in enumerate(np.asarray(out)):
                nonce = base + i * self.batch + int(off)
                if off < self.batch and nonce < stop:
                    return nonce
        return None
