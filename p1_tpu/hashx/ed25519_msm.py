"""Device-sharded Ed25519 batch verification: fe25519 in JAX limbs.

ROADMAP item 1, route (b): the TPU as a *validation* accelerator, not
just a miner.  This module evaluates the same subgroup-gated batch
equation as ``core/_ed25519.py::verify_batch`` — exact prime-subgroup
gates ([q]·P == identity) on every point plus one random-linear-
combination multi-scalar multiplication — as vectorized field
arithmetic over a device mesh:

- **fe25519 limbs**: field elements are 20 × 13-bit limbs in uint32
  (``FE_LIMBS``/``LIMB_BITS``).  13 bits is the TPU-honest radix: a
  limb product fits 26 bits and a 20-term column sum stays under 2³¹,
  so the whole pipeline runs in native int32/uint32 vector lanes — no
  64-bit integers, which TPUs do not carry.  ``fe_add``/``fe_sub``/
  ``fe_mul``/``fe_sq`` keep a limbs-≤-``LIMB_TOL`` invariant via
  parallel carry passes (carries ripple at most a few limbs per pass;
  three passes settle any product).
- **Point arithmetic**: the extended-coordinate add/double of
  ``core/_ed25519.py`` translated limb-wise and batched over a leading
  axis, so one `lax.scan` step advances EVERY point in the window.
- **Subgroup gate**: all points share the scalar q, so the gate is a
  scan over q's 64 fixed 4-bit windows — per step four batched doubles
  plus one table add (per-point 16-entry tables, the windowed form of
  ``_in_prime_subgroup``).
- **MSM**: windowed Pippenger in its SIMD shape — the per-point
  16-entry table IS the bucket set, indexed by each scalar's digit;
  per window one gather + one tree-reduction of batched point adds +
  four doubles of the accumulator (Horner over windows).  Work is
  ~(bits/4)·(N + N) point-additions for the whole batch, against
  ~770·N for serial ladders.
- **Sharding**: `shard_map` over the 1-D chip mesh
  (``hashx.sharded.make_mesh`` — the same seam the miner uses,
  SNIPPETS.md [1]/[3]): the point/scalar arrays split along the batch
  axis, every chip gates its shard and folds its partial MSM sum, and
  D partial points come back for a host-side combine (point addition
  is the reduction, so the cross-chip fold is D−1 cheap host adds, not
  a ``psum``).

Division of labor with the host (mirrors ``core/_ed25519_native.py``):
decompression (two ~255-bit field exponentiations — CPython's ``pow``
is C-speed), SHA-512 challenges, mod-q scalar products, and the random
coefficients stay on the host; the device does the O(bits·N) point
arithmetic, which is all the pure-Python path is slow at.

Semantics are the fallback batch's exactly — ``verify_batch_device``
accepts iff ``_ed25519.verify_batch`` would (2⁻¹²⁸ coefficients aside),
pinned by the torsion/corruption matrix in tests/test_ed25519_device.py
— so ``core/keys.py`` can route batches here (``--sig-backend device``)
with ``first_invalid``'s serial settlement unchanged.

Scope: on a single CPU host the mesh is virtual and this path measures
architecture cost, not speedup — the native C++ engine is the host fast
lane.  No chip cell measures this path yet (ROADMAP R9, D5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from p1_tpu.core import _ed25519 as _py
from p1_tpu.hashx import configure_compile_cache
from p1_tpu.hashx.sharded import AXIS, make_mesh

_U32 = jnp.uint32

#: Field-element shape: 20 limbs × 13 bits = 260 ≥ 255 bits.
FE_LIMBS = 20
LIMB_BITS = 13
LIMB_MASK = (1 << LIMB_BITS) - 1
#: 2^260 ≡ 19·2^5 (mod p): the fold factor for limb-19 overflow.
FOLD = 19 << (FE_LIMBS * LIMB_BITS - 255)
#: Carried limbs stay ≤ this (LIMB_MASK + a bounded fold residue); the
#: fe_mul column bound 20·LIMB_TOL² + fold terms < 2³¹ is what makes
#: uint32 accumulation safe.
LIMB_TOL = LIMB_MASK + 641

_SCALAR_WINDOWS = 64  # 256-bit scalars in 4-bit windows


def fe_from_int(x: int) -> np.ndarray:
    return np.array(
        [(x >> (LIMB_BITS * i)) & LIMB_MASK for i in range(FE_LIMBS)],
        dtype=np.uint32,
    )


def fe_to_int(limbs) -> int:
    total = 0
    for i, limb in enumerate(np.asarray(limbs, dtype=np.uint64)):
        total += int(limb) << (LIMB_BITS * i)
    return total % _py._P


def _carry_pass(x):
    """One parallel carry pass: every limb sheds its overflow to its
    neighbor (limb 19's overflow folds to limb 0 ×FOLD).  Carries can
    re-overflow a limb by a bounded amount; three passes settle any
    fe_mul column vector (bounds audited in the module docstring)."""
    c = x >> LIMB_BITS
    x = x & LIMB_MASK
    fold = c[..., FE_LIMBS - 1 :] * _U32(FOLD)
    return x + jnp.concatenate([fold, c[..., : FE_LIMBS - 1]], axis=-1)


def _carry(x, passes: int = 3):
    for _ in range(passes):
        x = _carry_pass(x)
    return x


def fe_add(a, b):
    return _carry(a + b, passes=2)


#: A multiple of p whose EVERY limb exceeds a carried operand limb
#: (≤ LIMB_TOL), so a−b+pad never underflows in uint32 while the
#: represented value shifts by 0 mod p.  Canonical p limbs will not do
#: (the top limb is only 255 — smaller than a reduced operand limb), so
#: the pad is 2·(p≪13) with the shifted-out top limb folded back via
#: 2^260 ≡ FOLD: limbs [2·255·FOLD, 2·p₀, 2·p₁, …, 2·p₁₈] ≥ 16346.
_P_LIMBS = [int(v) for v in fe_from_int(_py._P)]
_SUBPAD = tuple(2 * v for v in ([_P_LIMBS[-1] * FOLD] + _P_LIMBS[:-1]))
assert min(_SUBPAD) > LIMB_TOL


def fe_sub(a, b):
    pad = jnp.array(_SUBPAD, dtype=_U32)
    return _carry((a + pad) - b, passes=2)


def _fold_columns(cols):
    """39 convolution columns -> 20 limbs: high columns fold back by
    2^260 ≡ FOLD, split lo/hi so every product stays < 2³¹."""
    low = cols[..., :FE_LIMBS]
    high = cols[..., FE_LIMBS:]  # 19 columns
    hi_lo = high & LIMB_MASK
    hi_hi = high >> LIMB_BITS
    low = low.at[..., : FE_LIMBS - 1].add(hi_lo * _U32(FOLD))
    low = low.at[..., 1:FE_LIMBS].add(hi_hi * _U32(FOLD))
    # Four passes: product columns reach ~2^31, and the limb-0 fold can
    # re-inflate limb 0 to ~2^24 twice before the ripple dies out.
    return _carry(low, passes=4)


def fe_mul(a, b):
    """Schoolbook 20×20 limb product as a padded-shift convolution —
    20 batched multiplies + a tree sum, fully vectorized over the
    leading axes."""
    terms = []
    for i in range(FE_LIMBS):
        prod = a[..., i : i + 1] * b
        terms.append(
            jnp.pad(prod, [(0, 0)] * (prod.ndim - 1) + [(i, FE_LIMBS - 1 - i)])
        )
    return _fold_columns(sum(terms))


def fe_sq(a):
    """Square via the symmetric half: cross terms i<j counted once and
    doubled — ~half the multiplies of fe_mul."""
    terms = []
    for i in range(FE_LIMBS):
        diag = a[..., i : i + 1] * a[..., i : i + 1]
        terms.append(
            jnp.pad(
                diag,
                [(0, 0)] * (diag.ndim - 1)
                + [(2 * i, 2 * (FE_LIMBS - 1 - i))],
            )
        )
        if i + 1 < FE_LIMBS:
            cross = _U32(2) * a[..., i : i + 1] * a[..., i + 1 :]
            terms.append(
                jnp.pad(
                    cross,
                    [(0, 0)] * (cross.ndim - 1) + [(2 * i + 1, FE_LIMBS - 1 - i)],
                )
            )
    return _fold_columns(sum(terms))


#: Bits of the top limb below 2^255 (13·19 = 247 bits underneath).
_TOP_BITS = 255 - LIMB_BITS * (FE_LIMBS - 1)
_TOP_MASK = (1 << _TOP_BITS) - 1


def fe_canon(x):
    """Full reduction to the canonical representative (< p).

    The 260-bit limb capacity means a merely-carried value can still be
    ~32p (the top limb holds 13 bits where p uses 8), so: (1) settle
    the limbs exactly and fold the top limb's bits ≥ 2²⁵⁵ back as ×19 —
    twice, because the first fold can ripple — leaving the value < 2p;
    then (2) the +19 trick: x ≥ p iff x+19 crosses 2²⁵⁵, in which case
    adding 19 and dropping bit 255 IS the subtraction of p.  Sequential
    exact carries are fine here: canon runs on verdicts and final
    equalities, not inside the per-window arithmetic."""
    x = _carry(x, passes=4)
    for _ in range(2):
        limbs = [x[..., i] for i in range(FE_LIMBS)]
        c = jnp.zeros_like(limbs[0])
        for i in range(FE_LIMBS):
            t = limbs[i] + c
            limbs[i] = t & LIMB_MASK
            c = t >> LIMB_BITS
        limbs[0] = limbs[0] + c * _U32(FOLD)  # beyond-2^260 overflow
        hi = limbs[FE_LIMBS - 1] >> _TOP_BITS  # bits >= 2^255
        limbs[FE_LIMBS - 1] = limbs[FE_LIMBS - 1] & _TOP_MASK
        limbs[0] = limbs[0] + hi * _U32(19)
        x = jnp.stack(limbs, axis=-1)
    probe = x.at[..., 0].add(_U32(19))
    limbs = [probe[..., i] for i in range(FE_LIMBS)]
    c = jnp.zeros_like(limbs[0])
    for i in range(FE_LIMBS):
        t = limbs[i] + c
        limbs[i] = t & LIMB_MASK
        c = t >> LIMB_BITS
    q = (limbs[FE_LIMBS - 1] >> _TOP_BITS) & 1
    x = x.at[..., 0].add(_U32(19) * q)
    limbs = [x[..., i] for i in range(FE_LIMBS)]
    c = jnp.zeros_like(limbs[0])
    for i in range(FE_LIMBS):
        t = limbs[i] + c
        limbs[i] = t & LIMB_MASK
        c = t >> LIMB_BITS
    out = jnp.stack(limbs, axis=-1)
    return out.at[..., FE_LIMBS - 1].set(out[..., FE_LIMBS - 1] & _TOP_MASK)


def fe_eq(a, b):
    return jnp.all(fe_canon(a) == fe_canon(b), axis=-1)


def fe_is_zero(a):
    return jnp.all(fe_canon(a) == 0, axis=-1)


# ---------------------------------------------------------------- points --
# A batch of points is a (..., 4, FE_LIMBS) uint32 array — extended
# homogeneous (X, Y, Z, T), the exact formulas of core/_ed25519.py.

_D2 = tuple(int(v) for v in fe_from_int((2 * _py._D) % _py._P))


def ge_identity(shape=()):
    out = np.zeros(shape + (4, FE_LIMBS), dtype=np.uint32)
    out[..., 1, 0] = 1  # y = 1
    out[..., 2, 0] = 1  # z = 1
    return jnp.asarray(out)


def ge_add(p, q):
    px, py_, pz, pt = (p[..., i, :] for i in range(4))
    qx, qy, qz, qt = (q[..., i, :] for i in range(4))
    d2 = jnp.array(_D2, dtype=_U32)
    aa = fe_mul(fe_sub(py_, px), fe_sub(qy, qx))
    bb = fe_mul(fe_add(py_, px), fe_add(qy, qx))
    cc = fe_mul(fe_mul(pt, qt), d2)
    zz = fe_mul(pz, qz)
    dd = fe_add(zz, zz)
    e = fe_sub(bb, aa)
    f = fe_sub(dd, cc)
    g = fe_add(dd, cc)
    h = fe_add(bb, aa)
    return jnp.stack(
        [fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h)], axis=-2
    )


def ge_double(p):
    px, py_, pz, _ = (p[..., i, :] for i in range(4))
    aa = fe_sq(px)
    bb = fe_sq(py_)
    cc_ = fe_sq(pz)
    cc = fe_add(cc_, cc_)
    h = fe_add(aa, bb)
    e = fe_sub(h, fe_sq(fe_add(px, py_)))
    g = fe_sub(aa, bb)
    f = fe_add(cc, g)
    return jnp.stack(
        [fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h)], axis=-2
    )


def ge_is_identity(p):
    return fe_is_zero(p[..., 0, :]) & fe_eq(p[..., 1, :], p[..., 2, :])


def _point_table(points):
    """Per-point windowed table [0]P..[15]P: (16, N, 4, FE_LIMBS).
    Built with a scan (one ge_add body) rather than 14 unrolled adds —
    the unrolled form multiplied the traced graph by ~15× and XLA
    compile time on a small host with it."""

    def step(prev, _):
        nxt = ge_add(prev, points)
        return nxt, nxt

    _, rows = lax.scan(step, points, None, length=14)
    return jnp.concatenate(
        [ge_identity(points.shape[:-2])[None], points[None], rows], axis=0
    )


#: q in 4-bit windows, most significant first (shared gate scalar).
_Q_DIGITS = np.array(
    [(_py._Q >> (4 * i)) & 15 for i in reversed(range(_SCALAR_WINDOWS))],
    dtype=np.uint32,
)


def _gate_all(points):
    """[q]·P for every point in the batch — identity iff torsion-free.
    One scan over q's 64 windows; the per-step digit indexes every
    point's table at once (the digits are shared, so the lookup is a
    single dynamic slice, not a gather)."""
    table = _point_table(points)

    def step(acc, digit):
        for _ in range(4):
            acc = ge_double(acc)
        term = lax.dynamic_index_in_dim(table, digit, axis=0, keepdims=False)
        return ge_add(acc, term), ()

    acc0 = ge_identity(points.shape[:-2])
    acc, _ = lax.scan(step, acc0, jnp.asarray(_Q_DIGITS))
    return ge_is_identity(acc)


def _msm_tree(points, digit_rows):
    """Σ sᵢ·Pᵢ over the batch: windowed Pippenger in SIMD shape.

    ``digit_rows`` is (64, N) — each scalar's 4-bit windows, msb first.
    Per window: gather each point's bucket (its table row for its own
    digit), tree-reduce the batch to one point, Horner-accumulate.
    The batch size must be a power of two (callers pad with identity
    points and zero scalars, which add nothing)."""
    table = jnp.moveaxis(_point_table(points), 0, 1)  # (N, 16, 4, L)

    def step(acc, digits):
        for _ in range(4):
            acc = ge_double(acc)
        idx = digits.reshape(digits.shape + (1, 1, 1)).astype(jnp.int32)
        terms = jnp.take_along_axis(table, idx, axis=1)[:, 0]
        while terms.shape[0] > 1:
            half = terms.shape[0] // 2
            terms = ge_add(terms[:half], terms[half:])
        return ge_add(acc, terms[0]), ()

    acc, _ = lax.scan(step, ge_identity(), digit_rows)
    return acc


@functools.lru_cache(maxsize=8)
def _jit_gate_msm(mesh, per_device: int):
    """The fused device program: gate every point exactly, fold the
    shard's partial MSM — one `shard_map` over the chip mesh, arrays
    split on the batch axis.  Outputs stack per device: (D,) gate
    verdicts and (D, 4, L) partial sums the host combines (point
    addition is the cross-chip reduction, so it rides home as D tiny
    arrays rather than a collective)."""

    configure_compile_cache()

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)),
        check_vma=False,
        # check_vma off: the scan carries mix replicated constants
        # (q digits, curve constants) into varying shard data and the
        # varying-manual-axes checker wants per-op pcasts through the
        # whole fe pipeline — pure noise for an embarrassingly parallel
        # map with no collectives (the pallas miner body makes the same
        # call, hashx/sharded.py).
    )
    def program(points, digit_cols):
        ok = jnp.all(_gate_all(points))
        partial = _msm_tree(points, jnp.transpose(digit_cols))
        return ok[None], partial[None]

    del per_device  # part of the cache key: shapes bake into the jit
    return program


def _digits_of(scalar: int) -> np.ndarray:
    return np.array(
        [(scalar >> (4 * i)) & 15 for i in reversed(range(_SCALAR_WINDOWS))],
        dtype=np.uint32,
    )


def _encode_point(pt) -> np.ndarray:
    x, y, z, t = pt
    return np.stack(
        [fe_from_int(x), fe_from_int(y), fe_from_int(z), fe_from_int(t)]
    )


def _decode_point(arr):
    return tuple(fe_to_int(np.asarray(arr)[i]) for i in range(4))


@functools.lru_cache(maxsize=4)
def _default_mesh(n_devices: int | None = None):
    return make_mesh(n_devices)


def verify_batch_device(triples, mesh=None, n_devices: int | None = None) -> bool:
    """``_ed25519.verify_batch`` evaluated on the device mesh.

    Host side: parse + range-check, decompress (CPython pow is C-speed),
    draw the 128-bit coefficients, dedup pubkeys (one gate and ONE
    combined MSM term Σzᵢkᵢ·A per unique key — same point, scalars
    merge).  Device side: exact gates + partial MSMs per shard.  Host
    closes: D−1 partial adds, the base-point term, identity check.

    Accepts iff the fallback batch would (same gate, same combination,
    independent randomness) — False is NOT a serial verdict, exactly
    the ``verify_batch`` contract everywhere else.
    """
    import secrets

    triples = list(triples)
    if not triples:
        return True
    if mesh is None:
        mesh = _default_mesh(n_devices)
    points = []  # decompressed (x, y, z, t) int tuples
    scalars = []  # matching MSM coefficients
    a_slots: dict[bytes, int] = {}  # pubkey -> index into points
    s_total = 0
    for pubkey, sig, message in triples:
        if len(pubkey) != 32 or len(sig) != 64:
            return False
        s = int.from_bytes(sig[32:], "little")
        if s >= _py._Q:
            return False
        pubkey = bytes(pubkey)
        slot = a_slots.get(pubkey)
        if slot is None:
            a_pt = _py._pt_decompress(pubkey)
            if a_pt is None:
                return False
            slot = len(points)
            a_slots[pubkey] = slot
            points.append(a_pt)
            scalars.append(0)
        r_pt = _py._pt_decompress(sig[:32])
        if r_pt is None:
            return False
        k = int.from_bytes(_py._sha512(sig[:32] + pubkey + message), "little")
        k %= _py._Q
        z = secrets.randbits(128) | 1
        s_total = (s_total + z * s) % _py._Q
        # The mod-q merges are exact only because the device gate PROVES
        # every point has order q before the sum is trusted (the same
        # gate-first contract as every other backend).
        scalars[slot] = (scalars[slot] + z * k) % _py._Q
        points.append(r_pt)
        scalars.append(z)
    n_dev = mesh.devices.size
    per_device = max(1, -(-len(points) // n_dev))
    # power-of-two tiles keep the in-shard tree reduction exact
    per_device = 1 << (per_device - 1).bit_length()
    total = per_device * n_dev
    ident = (0, 1, 1, 0)
    while len(points) < total:
        points.append(ident)  # identity + zero scalar: contributes nothing
        scalars.append(0)
    pts = jnp.asarray(np.stack([_encode_point(p) for p in points]))
    digs = jnp.asarray(np.stack([_digits_of(s) for s in scalars]))
    program = _jit_gate_msm(mesh, per_device)
    # digits travel shard-major on axis 0 => pass as (N, 64) columns
    ok, partials = program(pts, digs)
    if not bool(jnp.all(ok)):
        return False
    acc = _py._IDENT
    for d in range(n_dev):
        acc = _py._pt_add(acc, _decode_point(partials[d]))
    if s_total:
        acc = _py._pt_add(acc, _py._pt_mul(_py._Q - s_total, _py._B))
    return _py._pt_equal(acc, _py._IDENT)
