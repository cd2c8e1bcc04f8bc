"""Pallas kernel: interpret-mode parity vs the cpu/numpy oracles.

The compiled Mosaic path needs real TPU hardware; these tests run the
IDENTICAL kernel through the Pallas interpreter on the CPU test platform
(asked for with ``interpret=True``), so every lane of the round math, the
target compare, and the first-hit min-reduction is checked without a chip.
Throughput of the compiled kernel is measured on the chip by ``perfbench``.
"""

import random

import pytest

from p1_tpu.core import BlockHeader, meets_target
from p1_tpu.hashx import get_backend

pytest.importorskip("jax.experimental.pallas")

DIFF = 8
BATCH = 1 << 12  # small steps: the interpreter is slow


def _prefix(seed: int) -> bytes:
    rng = random.Random(seed)
    return BlockHeader(
        1, rng.randbytes(32), rng.randbytes(32), 1735689700, DIFF, 0
    ).mining_prefix()


@pytest.fixture(scope="module")
def tpu_backend():
    be = get_backend("tpu", batch=BATCH, sub=8, interpret=True)
    assert be.interpret
    return be


class TestPallasParity:
    def test_registered_as_tpu(self, tpu_backend):
        assert tpu_backend.name == "tpu"

    @pytest.mark.parametrize("kwargs", [{}, {"batch": BATCH, "sub": 8}])
    def test_refuses_without_tpu_unless_interpret(self, kwargs):
        # No silent interpreter: off-TPU, only an explicit interpret=True
        # runs the kernel; the default raises and names what JAX found.
        with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
            get_backend("tpu", **kwargs)

    @pytest.mark.parametrize("seed", [0, 7])
    def test_first_hit_matches_cpu(self, tpu_backend, seed):
        prefix = _prefix(seed)
        got = tpu_backend.search(prefix, 0, BATCH, DIFF)
        want = get_backend("cpu").search(prefix, 0, BATCH, DIFF)
        assert got.nonce == want.nonce
        if want.nonce is not None:
            assert got.hashes_done == want.hashes_done
            sealed = prefix + int(got.nonce).to_bytes(4, "big")
            from p1_tpu.hashx.sha256_ref import sha256d

            assert meets_target(sha256d(sealed), DIFF)

    def test_every_nonce_hits_at_difficulty_zero(self, tpu_backend):
        # Tie-break: difficulty 0 makes every lane a hit; the kernel's
        # min-reduction must still return the earliest (the range start).
        res = tpu_backend.search(_prefix(1), 0, BATCH, 0)
        assert res.nonce == 0 and res.hashes_done == 1

    def test_nonce_start_offset(self, tpu_backend):
        prefix = _prefix(2)
        base = 0x1000
        res = tpu_backend.search(prefix, base, BATCH, 0)
        assert res.nonce == base

    def test_partial_final_step_masked(self, tpu_backend):
        # count smaller than the kernel batch: a hit reported beyond the
        # valid range must be discarded by the host-side mask.
        prefix = _prefix(3)
        full = get_backend("cpu").search(prefix, 0, BATCH, DIFF)
        if full.nonce is None:
            pytest.skip("no hit in range for this seed")
        short = tpu_backend.search(prefix, 0, full.nonce, DIFF)
        assert short.nonce is None
        exact = tpu_backend.search(prefix, 0, full.nonce + 1, DIFF)
        assert exact.nonce == full.nonce

    def test_batch_must_tile(self):
        with pytest.raises(ValueError, match="multiple"):
            get_backend("tpu", batch=1000, sub=8, interpret=True)

    def test_batch_int32_bound(self):
        with pytest.raises(ValueError, match="2\\*\\*31"):
            get_backend("tpu", batch=1 << 31, sub=8, interpret=True)

    @pytest.mark.parametrize("prior", [None, 0, 1])
    @pytest.mark.parametrize("difficulty", [DIFF, 255])
    def test_chained_step(self, difficulty, prior):
        # The prior flag set: the kernel skips every tile and the step
        # reads as a miss, its flag set for the step after it.  Clear: the
        # offset of the four-operand form, and a flag set only on a hit.
        import jax.numpy as jnp

        from p1_tpu.core import target_from_difficulty, target_to_words
        from p1_tpu.hashx import sha256_ref
        from p1_tpu.hashx.pallas_backend import jit_pallas_search_step

        prefix = _prefix(5)
        arrays = [jnp.array(words, dtype=jnp.uint32) for words in (
            sha256_ref.header_midstate(prefix), sha256_ref.header_tail_words(prefix),
            target_to_words(target_from_difficulty(difficulty)))]
        step = jit_pallas_search_step(BATCH, 8, interpret=True)
        truth = get_backend("cpu").search(prefix, 0, BATCH, difficulty).nonce
        want = BATCH if truth is None else truth
        if prior is None:
            assert int(step(*arrays, jnp.uint32(0))) == want
            return
        result = step(*arrays, jnp.uint32(0), jnp.array([BATCH, prior], jnp.uint32))
        offset, found = (int(x) for x in result)
        if prior:
            assert (offset, found) == (BATCH, 1)
        else:
            assert (offset, found) == (want, int(truth is not None))

    def test_odd_tile_disables_ramp(self):
        # sub=20 -> block 2560 doesn't divide the 2^22 ramp floor; the
        # backend must opt out of the opening ramp rather than crash on a
        # fresh low-difficulty search.
        be = get_backend("tpu", batch=2560 * 4, sub=20, interpret=True)
        assert be.ramp_floor is None
        res = be.search(_prefix(4), 0, 2560, 0)
        assert res.nonce == 0
