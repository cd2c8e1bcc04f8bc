"""VPU uint32 roofline for the Pallas SHA-256d search kernel.

Two measurements feed docs/PERF.md's "Roofline" section (VERDICT r4
item 3 asked for the denominator behind the MH/s headline):

1. ``--count``: a static op count of one SHA-256d candidate exactly as
   the kernel traces it (jax_sha256._compress round body), twice — naive
   "as written" (every shift/or/xor/add/and/not = 1), and a fold model
   where compile-time-constant subtrees fold away and all-scalar ops run
   on the scalar core instead of the VPU.  No hardware needed.

2. default: a Pallas microbenchmark measuring the VPU's achievable
   uint32 ALU rate with op mixes from pure adds to full SHA-round-like
   bodies.  Chains are mutually recursive (unfoldable), per-lane varying
   (unscalarizable), and grid-index-seeded (unhoistable) — each of those
   was observed to be silently optimized away without the countermeasure,
   inflating rates ~500x.  Run on the TPU: ``python benchmarks/
   vpu_roofline.py``.  Every timed repetition uses FRESH input values and
   times a forced ``int()`` scalar readback, so neither a cached result
   nor an enqueue-only return can pass for compute; configs are sized to
   ~0.4 s of compute so the dispatch is a small share of each timing.
"""

from __future__ import annotations

import argparse
import functools
import statistics
import time


# ---------------------------------------------------------------- op count

def count_ops() -> dict:
    """Static per-candidate op counts of the traced kernel math."""

    class T:  # tagged operand: C compile-time const, S scalar, V vector
        def __init__(self, kind):
            self.kind = kind

    count = {"V": 0, "S": 0, "naive": 0}

    def op(*args):
        count["naive"] += 1
        kinds = {a.kind for a in args}
        if kinds == {"C"}:
            return T("C")  # folds at compile time
        if "V" in kinds:
            count["V"] += 1
            return T("V")
        count["S"] += 1  # scalar-core op, off the VPU
        return T("S")

    def rotr(x):  # two shifts + or, as _rotr writes it
        return op(op(x), op(x))

    def xor3(a, b, c):
        return op(op(a, b), c)

    def compress(state, w):
        s, w = list(state), list(w)
        for _ in range(64):
            a, b, c, d, e, f, g, h = s
            s1 = xor3(rotr(e), rotr(e), rotr(e))
            ch = op(op(e, f), op(op(e), g))  # (e&f) ^ (~e & g)
            t1 = op(op(op(op(h, s1), ch), T("C")), w[0])  # + k + w0
            s0 = xor3(rotr(a), rotr(a), rotr(a))
            maj = xor3(op(a, b), op(a, c), op(b, c))
            sig0 = xor3(rotr(w[1]), rotr(w[1]), op(w[1]))
            sig1 = xor3(rotr(w[14]), rotr(w[14]), op(w[14]))
            w_next = op(op(op(w[0], sig0), w[9]), sig1)
            s = [op(op(t1, s0), maj), a, b, c, op(d, t1), e, f, g]
            w = w[1:] + [w_next]
        return [op(x, y) for x, y in zip(state, s)]

    # Pass 1 chunk 2: midstate/tail are runtime scalars, nonce is the one
    # vector input, padding/length are constants.
    state1 = compress(
        [T("S")] * 8, [T("S")] * 3 + [T("V")] + [T("C")] * 12
    )
    # Pass 2: the digest words are vectors, padding constants, IV constant.
    digest = compress([T("C")] * 8, state1 + [T("C")] * 8)
    # Target check (below_target): per word cmp, and, or, cmp, and.
    for d in digest:
        op(d, T("S")), op(T("V")), op(T("V")), op(d, T("S")), op(T("V"))
    for _ in range(6):  # flat-nonce computation + where/min plumbing
        op(T("V"))
    return count


# ------------------------------------------------------------- microbench

SUB = 16  # tile rows, same as the search kernel
OPS_PER_ITER = {"add": 2, "rot": 8, "sha": 11, "round": 30}


def _bench_kernel(seed_ref, out_ref, *, iters, chains, mix):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    U32 = jnp.uint32
    rows = jax.lax.broadcasted_iota(U32, (SUB, 128), 0)
    cols = jax.lax.broadcasted_iota(U32, (SUB, 128), 1)
    lane = rows * U32(128) + cols
    gi = pl.program_id(0).astype(U32)
    xs = [seed_ref[j] + lane + gi * U32(0x85EBCA6B) for j in range(chains)]
    ys = [
        (seed_ref[j] ^ U32(0x9E3779B9)) + (lane ^ gi) * U32(2654435761)
        for j in range(chains)
    ]

    def rot(v, n):
        return (v >> U32(n)) | (v << U32(32 - n))

    def one(x, y):
        if mix == "add":  # 2 ops/iter
            x = x + y
            y = y ^ x
        elif mix == "rot":  # 8 ops/iter
            x = rot(x, 7) ^ rot(y, 18)
            y = y + x
        elif mix == "sha":  # σ0-like, 11 ops/iter
            s = rot(x, 7) ^ rot(x, 18) ^ (x >> U32(3))
            x = s ^ y
            y = y + x
        elif mix == "round":  # SHA-round-like body, 30 ops/iter
            s1 = rot(x, 6) ^ rot(x, 11) ^ rot(x, 25)
            ch = (x & y) ^ (~x & (y + U32(1)))
            t1 = y + s1 + ch + U32(0x428A2F98)
            s0 = rot(t1, 2) ^ rot(t1, 13) ^ rot(t1, 22)
            x = t1 + s0
            y = y ^ x
        return x, y

    INNER = 16  # python-unrolled (Mosaic fori_loop: unroll=1 or full only)

    def body(_, carry):
        xs, ys = carry
        for _ in range(INNER):
            pairs = [one(x, y) for x, y in zip(xs, ys)]
            xs, ys = [p[0] for p in pairs], [p[1] for p in pairs]
        return xs, ys

    xs, ys = jax.lax.fori_loop(0, iters // INNER, body, (xs, ys), unroll=1)
    acc = xs[0]
    for v in xs[1:] + ys:
        acc = acc ^ v
    red = jnp.min(acc.astype(jnp.int32))
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[0] = jnp.int32(0)

    out_ref[0] = out_ref[0] ^ red


@functools.cache
def _make_bench(grid, iters, chains, mix):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kern = functools.partial(
        _bench_kernel, iters=iters, chains=chains, mix=mix
    )
    call = pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
    )
    return jax.jit(lambda s: call(s))


def run_bench() -> None:
    import jax
    import jax.numpy as jnp

    print("backend:", jax.default_backend(), jax.devices()[0])

    def measure(mix, chains, grid=512, reps=3):
        ops = OPS_PER_ITER[mix]
        iters = max(
            256, int(2.0e12 / (grid * chains * SUB * 128 * ops)) // 16 * 16
        )
        fn = _make_bench(grid, iters, chains, mix)
        base = jnp.arange(1, chains + 1, dtype=jnp.uint32) * jnp.uint32(
            0x01000193
        )
        int(fn(base)[0])  # compile + warm, forced readback
        best = 1e9
        for k in range(reps):
            seeds = base + jnp.uint32(k + 1)  # fresh values every rep
            t0 = time.perf_counter()
            int(fn(seeds)[0])  # timing a forced readback, see module doc
            best = min(best, time.perf_counter() - t0)
        rate = grid * iters * chains * SUB * 128 * ops / best
        return rate, best

    print(f"{'mix':>6} {'chains':>6} {'wall_s':>7} {'Top/s wall':>11}")
    rates = []
    for mix in ("add", "rot", "sha", "round"):
        for chains in (4, 8):
            rate, t = measure(mix, chains)
            rates.append(rate)
            print(f"{mix:>6} {chains:>6} {t:7.3f} {rate/1e12:11.2f}")
    med = statistics.median(rates)
    print(f"\nmedian wall rate: {med/1e12:.2f} Top/s "
          f"(compute-only ≈ wall × wall_s/(wall_s - dispatch))")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--count", action="store_true", help="op count only")
    args = ap.parse_args()
    c = count_ops()
    print(
        f"per-candidate SHA-256d ops as traced: naive {c['naive']} "
        f"(every shift/or/xor/add/and/not = 1); fold model: "
        f"{c['V']} vector ops on the VPU + {c['S']} scalar-core ops"
    )
    if not args.count:
        run_bench()
