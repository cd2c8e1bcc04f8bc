"""SHA-256d nonce search as pure JAX uint32 math.

The device-side heart of the framework (BASELINE.json:5 — the miner's inner
loop "becomes a vmapped Pallas SHA-256 kernel that evaluates millions of
candidate nonces per device step").  This module is the XLA formulation: one
uint32 lane per candidate nonce, vector ops that XLA tiles onto the TPU VPU
(8x128 vregs).  The Pallas kernel (pallas_backend.py) reuses exactly this
round math inside a kernel body; on CPU the same functions run under the
virtual-device test mesh.

Design choices for TPU:

- **Midstate**: the first 64 header bytes are nonce-independent, so the host
  compresses chunk 1 once (sha256_ref.header_midstate) and the device only
  runs chunk 2 + the full second pass — 2 compressions instead of 3.
- **Rolled rounds with an unroll knob**: the 64 SHA-256 rounds (with the
  message-schedule extension fused in) run under ``lax.fori_loop`` carrying a
  16-word rolling window — the whole double hash traces as ~2 round bodies
  instead of 2x(48+64) unrolled steps, so XLA:CPU compiles in seconds rather
  than tens of minutes (a 1-vCPU box never finished the unrolled trace).
  ``unroll=`` re-expands the loop body for TPU throughput; with the window
  carried as 16 separate arrays the rotation is pure re-binding, so an
  unrolled body has static register assignments and no roll/concat traffic.
- **Static shapes**: the batch size is a trace-time constant; the host loop
  re-invokes the jitted step with a new ``nonce_base`` scalar, so nothing
  recompiles between steps.
- **First-hit reduce**: each step returns ``min(flat lane index where hit,
  else batch)`` — a single uint32 — keeping device->host traffic at 4 bytes
  per step and making the multi-chip ``pmin`` reduction trivial.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from p1_tpu.hashx.sha256_ref import IV, K

_U32 = jnp.uint32

#: fori_loop unroll factor by platform.  CPU wants a tiny trace (compile
#: time dominates on the 1-vCPU test box); TPU amortizes one compile over
#: the whole mining session, so re-expanding the round body buys VPU
#: throughput back.  16 rounds per body keeps the trace ~8x smaller than
#: full unroll while giving XLA long straight-line stretches to fuse.
_PLATFORM_UNROLL = {"cpu": 1, "tpu": 16}


def default_unroll(platform: str | None = None) -> int:
    p = platform or jax.default_backend()
    return _PLATFORM_UNROLL.get(p, 8)


def _rotr(x: jax.Array, n: int) -> jax.Array:
    return (x >> _U32(n)) | (x << _U32(32 - n))


# A host-side constant (NOT a jnp array: creating one inside a trace and
# caching it would leak a tracer); jnp indexes it as an implicit constant.
_K_NP = np.asarray(K, dtype=np.uint32)


def _compress(
    state: tuple[jax.Array, ...],
    w16: tuple[jax.Array, ...],
    unroll: int,
    ks=None,
) -> tuple[jax.Array, ...]:
    """One SHA-256 compression over a 16-word chunk, rounds+extension fused.

    The carry holds the sliding window ``w[i..i+15]`` as 16 separate arrays;
    round ``i`` consumes ``w[i]`` (= window[0]) and appends
    ``w[i+16] = w[i] + σ0(w[i+1]) + w[i+9] + σ1(w[i+14])`` — so rounds
    16..63 see exactly the words the schedule extension would have produced,
    without ever materializing a (64, batch) array in HBM.  The 16 extension
    steps computed for rounds 48..63 feed nothing; that waste is ~12% of the
    σ work and buys a single uniform round body.
    """
    if ks is None:
        ks = jnp.asarray(_K_NP)
    # ``ks`` may also be a Pallas SMEM ref of the K table: inside a kernel
    # a captured jnp constant is disallowed, so the kernel passes the table
    # in as a scalar-memory input and round ``i`` reads ``ks[i]``.

    def body(i, carry):
        w, s = carry
        a, b, c, d, e, f, g, h = s
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + ks[i] + w[0]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        sig0 = _rotr(w[1], 7) ^ _rotr(w[1], 18) ^ (w[1] >> _U32(3))
        sig1 = _rotr(w[14], 17) ^ _rotr(w[14], 19) ^ (w[14] >> _U32(10))
        w_next = w[0] + sig0 + w[9] + sig1
        return (
            w[1:] + (w_next,),
            (t1 + s0 + maj, a, b, c, d + t1, e, f, g),
        )

    _, out = lax.fori_loop(0, 64, body, (w16, tuple(state)), unroll=unroll)
    return tuple(x + y for x, y in zip(state, out))


def sha256d_words(
    midstate: jax.Array,
    tail: jax.Array,
    nonces: jax.Array,
    unroll: int | None = None,
) -> list[jax.Array]:
    """SHA-256d digest words for a lane-vector of nonces.

    midstate: (8,) uint32 chunk-1 state; tail: (3,) uint32 chunk-2 words 0..2;
    nonces: (...,) uint32.  Returns 8 arrays shaped like ``nonces``.
    """
    if unroll is None:
        unroll = default_unroll()
    zero = jnp.zeros_like(nonces)

    def bc(word: jax.Array) -> jax.Array:
        return jnp.broadcast_to(word.astype(_U32), nonces.shape)

    # Pass 1, chunk 2: 16 tail bytes + nonce word + pad(0x80) + bitlen 640.
    w = (bc(tail[0]), bc(tail[1]), bc(tail[2]), nonces)
    w += (zero + _U32(0x80000000),) + (zero,) * 10 + (zero + _U32(640),)
    state1 = _compress(tuple(bc(m) for m in midstate), w, unroll)

    # Pass 2: the 32-byte digest as one padded block (bitlen 256).
    w2 = state1 + (zero + _U32(0x80000000),) + (zero,) * 6 + (zero + _U32(256),)
    # Derive the IV lanes from ``zero`` (not jnp.full) so they inherit the
    # nonces' varying-manual-axes under shard_map and the fori_loop carry
    # types line up on multi-chip meshes.
    iv = tuple(zero + _U32(v) for v in IV)
    return list(_compress(iv, w2, unroll))


def sha256d_headers(
    header_words: jax.Array, unroll: int | None = None
) -> list[jax.Array]:
    """SHA-256d digests for a batch of full 80-byte headers.

    Unlike the nonce search (fixed prefix, varying nonce), every header
    differs in all 20 words, so all 3 compressions run on device: chunk 1
    (words 0..15), chunk 2 (words 16..19 + padding, bitlen 640), then the
    second pass over the 32-byte digest.  This is the chain-replay hot loop
    (BASELINE.json:9 — "verify 10k-block header chain, hash-only") as one
    batched device computation: ``header_words`` is (N, 20) uint32, returns
    8 arrays of shape (N,).
    """
    if unroll is None:
        unroll = default_unroll()
    n = header_words.shape[0]
    zero = jnp.zeros((n,), dtype=_U32)

    w1 = tuple(header_words[:, i] for i in range(16))
    iv = tuple(zero + _U32(v) for v in IV)
    state1 = _compress(iv, w1, unroll)

    w2 = tuple(header_words[:, i] for i in range(16, 20))
    w2 += (zero + _U32(0x80000000),) + (zero,) * 10 + (zero + _U32(640),)
    state2 = _compress(state1, w2, unroll)

    w3 = state2 + (zero + _U32(0x80000000),) + (zero,) * 6 + (zero + _U32(256),)
    return list(_compress(iv, w3, unroll))


def _verify_segment(
    header_words: jax.Array,
    target_words: jax.Array,
    prev_digest: jax.Array,
    genesis_first: jax.Array,
    difficulty: jax.Array,
    unroll: int | None,
) -> tuple[jax.Array, jax.Array]:
    """(first-invalid index or N, last header's digest words (8,))."""
    digests = sha256d_headers(header_words, unroll)
    n = header_words.shape[0]
    pow_ok = below_target(digests, target_words)
    pow_ok = pow_ok.at[0].set(pow_ok[0] | genesis_first)
    # The difficulty field itself is consensus data: a header claiming a
    # different difficulty than the chain's must be flagged even if its
    # hash happens to meet the real target (word 18 = difficulty, see
    # p1_tpu/core/header.py layout).
    pow_ok = pow_ok & (header_words[:, 18] == difficulty)

    link_ok = jnp.ones((n,), dtype=jnp.bool_)
    for w in range(8):
        claimed = header_words[:, 1 + w]
        actual = jnp.concatenate(
            [prev_digest[w][None], digests[w][:-1]]
        )
        link_ok = link_ok & (claimed == actual)

    ok = pow_ok & link_ok
    idx = jnp.arange(n, dtype=_U32)
    first_bad = jnp.min(jnp.where(ok, _U32(n), idx))
    last_digest = jnp.stack([d[-1] for d in digests])
    return first_bad, last_digest


def verify_header_chain(
    header_words: jax.Array,
    target_words: jax.Array,
    prev_digest: jax.Array,
    genesis_first: jax.Array,
    difficulty: jax.Array,
    unroll: int | None = None,
) -> jax.Array:
    """Index of the first invalid header in a linked batch, or N if all pass.

    ``header_words``: (N, 20) uint32 — consecutive headers of one chain
    segment.  A header is valid iff its declared difficulty field (word 18)
    equals ``difficulty``, its SHA-256d meets ``target_words`` AND its
    prev-hash field (words 1..8) equals the previous header's digest.
    ``prev_digest``: (8,) digest of the header before the segment (for i=0).
    ``genesis_first``: scalar bool — when true, header 0 is a genesis block:
    linkage (zero prev-hash) is still enforced via ``prev_digest`` but the
    PoW check is waived (genesis anchors by identity, not work).
    """
    idx, _ = _verify_segment(
        header_words, target_words, prev_digest, genesis_first, difficulty, unroll
    )
    return idx


def verify_header_chain_segments(
    words3: jax.Array,
    target_words: jax.Array,
    difficulty: jax.Array,
    unroll: int | None = None,
) -> jax.Array:
    """Whole-chain verification as ONE device program: ``lax.scan`` over
    (S, segment, 20) header words, carrying the cross-segment digest on
    device.  Returns (S,) local first-invalid indices (= segment when the
    segment is clean).

    The scan costs one dispatch and one bulk transfer for the entire chain,
    with no host re-hashing between segments; it was sized for a
    remote-device runtime where each dispatch cost ~125 ms.  Whether one
    dispatch still beats overlapped chunks on a directly attached chip is
    an open question (PERF.md).  Header 0 of segment 0 is treated as
    genesis (PoW waived, zero prev-hash enforced).
    """
    s = words3.shape[0]
    first_flags = jnp.arange(s) == 0

    def body(prev_digest, inp):
        seg_words, is_first = inp
        idx, last_digest = _verify_segment(
            seg_words, target_words, prev_digest, is_first, difficulty, unroll
        )
        return last_digest, idx

    _, idxs = lax.scan(
        body, jnp.zeros((8,), _U32), (words3, first_flags)
    )
    return idxs


@functools.cache
def jit_verify_chain_scan(
    n_segments: int,
    segment: int,
    platform: str | None = None,
    unroll: int | None = None,
):
    """Jitted ``verify_header_chain_segments`` for an (S, segment) layout."""
    if unroll is None:
        unroll = default_unroll(platform)
    fn = functools.partial(verify_header_chain_segments, unroll=unroll)
    device = jax.devices(platform)[0] if platform else None
    return jax.jit(fn, device=device)


def below_target(digest_words: list[jax.Array], target_words: jax.Array) -> jax.Array:
    """Lanes whose 256-bit big-endian digest is < the 8-word target."""
    lt = jnp.zeros(digest_words[0].shape, dtype=jnp.bool_)
    eq = jnp.ones(digest_words[0].shape, dtype=jnp.bool_)
    for i in range(8):
        tw = target_words[i]
        lt = lt | (eq & (digest_words[i] < tw))
        eq = eq & (digest_words[i] == tw)
    return lt


def first_hit_index(hits: jax.Array, batch: int) -> jax.Array:
    """min(flat lane index where hit) or ``batch`` if no lane hit (uint32)."""
    lanes = jnp.arange(batch, dtype=_U32).reshape(hits.shape)
    return jnp.min(jnp.where(hits, lanes, _U32(batch)))


def search_step(
    midstate: jax.Array,
    tail: jax.Array,
    target_words: jax.Array,
    nonce_base: jax.Array,
    prior: jax.Array | None = None,
    *,
    batch: int,
    unroll: int | None = None,
):
    """One device step: scan [nonce_base, nonce_base+batch) lanes, return
    the first hit's offset from nonce_base, or ``batch`` if none.

    Chained form: with ``prior``, the result of the step launched before,
    it returns its own result, uint32 ``(offset, found)`` in one array, and
    skips the scan, reading as a miss, when the prior's ``found`` is set;
    ``found`` is 1 when this step hit or skipped."""

    def scan(nonce_base):
        nonces = nonce_base + jnp.arange(batch, dtype=_U32)
        hits = below_target(
            sha256d_words(midstate, tail, nonces, unroll), target_words
        )
        return first_hit_index(hits, batch)

    if prior is None:
        return scan(nonce_base)
    skipped = prior[1] != 0
    # The miss is built from ``nonce_base`` so that under shard_map both
    # branches vary over the mesh alike.
    offset = lax.cond(skipped, lambda b: b * _U32(0) + _U32(batch), scan, nonce_base)
    return jnp.stack([offset, (skipped | (offset < _U32(batch))).astype(_U32)])


@functools.cache
def jit_search_step(batch: int, platform: str | None = None, unroll: int | None = None):
    """Jitted ``search_step`` closed over a static batch size: the plain
    four-operand step, or the chained one when called with ``prior``.

    ``unroll=None`` resolves per platform (see ``default_unroll``) before
    the trace is cut, so CPU tests get the second-scale compile and TPU
    keeps its throughput body.
    """
    if unroll is None:
        unroll = default_unroll(platform)
    fn = functools.partial(search_step, batch=batch, unroll=unroll)
    device = jax.devices(platform)[0] if platform else None
    return jax.jit(fn, device=device)
