"""JAX backend: digest parity vs NumPy oracle, search parity vs cpu backend."""

import random
import struct

import numpy as np
import pytest

from p1_tpu.core import BlockHeader, meets_target, target_from_difficulty, target_to_words
from p1_tpu.hashx import get_backend
from p1_tpu.hashx import sha256_ref

jax = pytest.importorskip("jax")
jnp = jax.numpy

from p1_tpu.hashx import jax_sha256  # noqa: E402

# One shape-specialized compile shared by the digest tests; eager dispatch of
# the unrolled 64-round trace is painfully slow on CPU.
_digest_jit = jax.jit(jax_sha256.sha256d_words)


def _prefix(seed: int) -> bytes:
    rng = random.Random(seed)
    return BlockHeader(
        1, rng.randbytes(32), rng.randbytes(32), 1735689700, 8, 0
    ).mining_prefix()


def _arrays(prefix: bytes, difficulty: int):
    midstate = jnp.array(sha256_ref.header_midstate(prefix), dtype=jnp.uint32)
    tail = jnp.array(sha256_ref.header_tail_words(prefix), dtype=jnp.uint32)
    target = jnp.array(
        target_to_words(target_from_difficulty(difficulty)), dtype=jnp.uint32
    )
    return midstate, tail, target


from p1_tpu.hashx.backend import HashBackend  # noqa: E402
from p1_tpu.hashx.jax_backend import PipelinedSearchMixin  # noqa: E402


class _SpanSpyBackend(PipelinedSearchMixin, HashBackend):
    """Records the span of every device step instead of hashing."""

    def __init__(self, step_span):
        self.step_span = step_span
        self.spans = []

    def _make_step(self, span):
        self.spans.append(span)

        def step(midstate, tail, target, base, prior):
            return jnp.array([span, prior[1]], jnp.uint32)  # never a hit

        return step


class TestOpeningRamp:
    """The adaptive opening ramp (VERDICT r2 #4): fresh low-difficulty scans
    start small and grow; throughput scans skip the ramp entirely."""

    def _scan(self, step_span, count, difficulty, nonce_start=0):
        be = _SpanSpyBackend(step_span)
        prefix = _prefix(0)
        be.search(prefix, nonce_start, count, difficulty)
        return be.spans

    def test_fresh_easy_scan_ramps_geometrically(self):
        from p1_tpu.hashx.jax_backend import _RAMP_FACTOR, _RAMP_FLOOR

        spans = self._scan(1 << 27, 1 << 28, difficulty=20)
        assert spans[0] == _RAMP_FLOOR
        assert spans[1] == _RAMP_FLOOR * _RAMP_FACTOR
        assert max(spans) == 1 << 27  # caps at the full batch
        assert spans == sorted(spans)  # non-decreasing

    def test_hit_inside_opening_step_reported_exactly(self):
        from p1_tpu.hashx.jax_backend import _RAMP_FLOOR

        class _HitAt(_SpanSpyBackend):
            def __init__(self, step_span, hit_offset):
                super().__init__(step_span)
                self.hit_offset = hit_offset

            def _make_step(self, span):
                self.spans.append(span)
                off = self.hit_offset

                def step(midstate, tail, target, base, prior):
                    return jnp.array([min(off, span), off < span], jnp.uint32)

                return step

        be = _HitAt(1 << 27, hit_offset=1234)
        res = be.search(_prefix(0), 0, 1 << 28, 20)
        # The hit lands inside the FIRST (small) ramp step, and the nonce /
        # hashes_done accounting must reflect the ramped span, not the
        # full batch.
        assert res.nonce == 1234
        assert res.hashes_done == 1235
        assert be.spans[0] == _RAMP_FLOOR

    def test_high_difficulty_scan_skips_ramp(self):
        spans = self._scan(1 << 27, 1 << 28, difficulty=255)
        assert all(s == 1 << 27 for s in spans)

    def test_resumed_range_skips_ramp(self):
        spans = self._scan(1 << 27, 1 << 27, difficulty=20, nonce_start=1 << 27)
        assert all(s == 1 << 27 for s in spans)

    def test_small_backend_never_ramps(self):
        from p1_tpu.hashx.jax_backend import _RAMP_FLOOR

        spans = self._scan(_RAMP_FLOOR // 2, _RAMP_FLOOR, difficulty=20)
        assert all(s == _RAMP_FLOOR // 2 for s in spans)


class _Planted(_SpanSpyBackend):
    """A device whose hits are planted nonces; it keeps the chained
    contract (skip on a set prior) and records each launch as
    ``(span, base, skipped)``."""

    def __init__(self, step_span, hits):
        super().__init__(step_span)
        self.ramp_floor = 512
        self.hits = sorted(hits)
        self.launched = []

    def _make_step(self, span):
        def step(midstate, tail, target, base, prior):
            skipped = bool(prior[1])
            self.launched.append((span, int(base), skipped))
            first = next((h - int(base) for h in self.hits
                          if int(base) <= h < int(base) + span), span)
            off = span if skipped else first
            return jnp.array([off, skipped or off < span], jnp.uint32)

        return step


class TestChainedSteps:
    """The host loop chains each launch to the one before: results equal
    a plain scan of the range, and exactly the launches behind the hit
    skip their work."""

    @pytest.mark.parametrize("start,count,hits", [
        (0, 1 << 15, [300, 1000]),  # the first ramp step (512)
        (0, 1 << 15, [512 + 4096 + 17]),  # a middle step
        (4096, 3 * 4096, [4096 + 2 * 4096 + 5]),  # the full last step
        (4096, 2 * 4096 + 100, [4096 + 2 * 4096 + 200]),  # beyond a partial last step
        (4096, 2 * 4096 + 100, [4096 + 2 * 4096 + 50]),  # inside it
    ], ids=["first-ramp", "middle", "full-last", "partial-beyond", "partial-inside"])
    def test_matches_a_plain_scan(self, start, count, hits):
        be = _Planted(4096, hits)
        res = be.search(_prefix(0), start, count, 20)
        want = min((h for h in hits if start <= h < start + count), default=None)
        assert res.nonce == want
        assert res.hashes_done == (count if want is None else want - start + 1)
        # A launch skips exactly when a launch before it in the call hit.
        hit_at = [i for i, (span, base, _) in enumerate(be.launched)
                  if any(base <= h < base + span for h in hits)]
        skipped = [s for *_, s in be.launched]
        first = hit_at[0] if hit_at else len(skipped)
        assert skipped == [False] * (first + 1) + [True] * (len(skipped) - first - 1)
        s = res.steps
        assert (s.launches_skipped, s.nonces_skipped) == (
            s.launches_abandoned, s.nonces_abandoned)
        assert s.launches_skipped == sum(skipped)

    def test_no_launch_is_chained_to_a_partial_one(self):
        from p1_tpu.hashx.jax_backend import _Launch

        result = jnp.array([5, 1], jnp.uint32)
        assert _Launch(0, 0, 4096, 4096, result).prior() is result
        with pytest.raises(AssertionError, match="partial"):
            _Launch(0, 0, 100, 4096, jnp.array([200, 1], jnp.uint32)).prior()


class TestJaxSha256:
    def test_digest_words_match_reference(self):
        prefix = _prefix(10)
        midstate, tail, _ = _arrays(prefix, 8)
        nonces = jnp.array([0, 1, 99999, 0xFFFFFFFF], dtype=jnp.uint32)
        words = _digest_jit(midstate, tail, nonces)
        for lane, nonce in enumerate([0, 1, 99999, 0xFFFFFFFF]):
            expect = sha256_ref.sha256d(prefix + struct.pack(">I", nonce))
            got = struct.pack(">8I", *(int(w[lane]) for w in words))
            assert got == expect, f"nonce {nonce:#x}"

    def test_search_step_finds_earliest(self):
        prefix = _prefix(11)
        difficulty = 8
        midstate, tail, target = _arrays(prefix, difficulty)
        batch = 1024
        step = jax_sha256.jit_search_step(batch)
        idx = int(step(midstate, tail, target, jnp.uint32(0)))
        truth = get_backend("cpu").search(prefix, 0, batch, difficulty)
        if truth.nonce is None:
            assert idx == batch
        else:
            assert idx == truth.nonce

    def test_search_step_no_hit_returns_batch(self):
        prefix = _prefix(12)
        midstate, tail, target = _arrays(prefix, 255)
        step = jax_sha256.jit_search_step(1024)
        assert int(step(midstate, tail, target, jnp.uint32(0))) == 1024

    def test_nonce_base_wraps_uint32(self):
        prefix = _prefix(13)
        midstate, tail, _ = _arrays(prefix, 8)
        # Lane math at the top of nonce space must wrap mod 2**32 like uint32.
        nonces = jnp.uint32(0xFFFFFFFE) + jnp.arange(4, dtype=jnp.uint32)
        words = _digest_jit(midstate, tail, nonces)
        expect = sha256_ref.sha256d(prefix + struct.pack(">I", 1))
        got = struct.pack(">8I", *(int(w[3]) for w in words))
        assert got == expect


class TestJaxBackend:
    def test_registry_name(self):
        backend = get_backend("jax", batch=4096)
        assert backend.name == "jax"

    def test_search_parity_with_cpu(self):
        backend = get_backend("jax", batch=1024)
        prefix = _prefix(14)
        truth = get_backend("cpu").search(prefix, 0, 1 << 14, 10)
        got = backend.search(prefix, 0, 1 << 14, 10)
        assert got.nonce == truth.nonce
        if got.nonce is not None:
            assert got.hashes_done == truth.hashes_done  # earliest-hit count

    def test_partial_final_batch_masked(self):
        # count smaller than one device batch: hits past count must not report.
        backend = get_backend("jax", batch=4096)
        prefix = _prefix(15)
        truth = get_backend("cpu").search(prefix, 0, 4096, 8)
        assert truth.nonce is not None, "seed must produce a hit in 4096"
        res = backend.search(prefix, 0, truth.nonce, 8)  # exclusive of the hit
        assert res.nonce is None
        res2 = backend.search(prefix, 0, truth.nonce + 1, 8)
        assert res2.nonce == truth.nonce

    def test_hit_meets_target(self):
        backend = get_backend("jax", batch=1024)
        prefix = _prefix(16)
        res = backend.search(prefix, 0, 1 << 13, 9)
        if res.nonce is not None:
            digest = sha256_ref.sha256d(prefix + struct.pack(">I", res.nonce))
            assert meets_target(digest, 9)

    def test_nonzero_start(self):
        backend = get_backend("jax", batch=1024)
        prefix = _prefix(17)
        truth = get_backend("cpu").search(prefix, 5000, 1 << 13, 9)
        got = backend.search(prefix, 5000, 1 << 13, 9)
        assert got.nonce == truth.nonce

    def test_batch_validation(self):
        with pytest.raises(ValueError):
            get_backend("jax", batch=1000)  # not a power of two
