"""Hash layer: FIPS 180-4 vectors, hashlib cross-checks, registry, search parity."""

import hashlib
import os
import random
import struct

import numpy as np
import pytest

from p1_tpu.core import BlockHeader, meets_target
from p1_tpu.hashx import available_backends, get_backend
from p1_tpu.hashx import sha256_ref
from p1_tpu.hashx.numpy_backend import lanes_below_target, sha256d_lanes

# FIPS 180-4 / NIST CAVP known-answer vectors for SHA-256.
FIPS_VECTORS = [
    (b"", "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"),
    (b"abc", "ba7816bf8f01cfea414140de5dae2223b00361a396177a9cb410ff61f20015ad"),
    (
        b"abcdbcdecdefdefgefghfghighijhijkijkljklmklmnlmnomnopnopq",
        "248d6a61d20638b8e5c026930c3e6039a33ce45964ff2167f6ecedd419db06c1",
    ),
    (b"a" * 1_000_000, "cdc76e5c9914fb9281a1c7e284d73e67f1809a48a497200e046d39ccc7112cd0"),
]


class TestSha256Ref:
    @pytest.mark.parametrize("msg,hexdigest", FIPS_VECTORS)
    def test_fips_vectors(self, msg, hexdigest):
        assert sha256_ref.sha256(msg).hex() == hexdigest

    def test_random_lengths_match_hashlib(self):
        rng = random.Random(0)
        for n in [1, 55, 56, 63, 64, 65, 119, 120, 127, 128, 200, 1000]:
            data = rng.randbytes(n)
            assert sha256_ref.sha256(data) == hashlib.sha256(data).digest()
            assert (
                sha256_ref.sha256d(data)
                == hashlib.sha256(hashlib.sha256(data).digest()).digest()
            )

    def test_midstate_reconstructs_header_hash(self):
        rng = random.Random(1)
        header = BlockHeader(2, rng.randbytes(32), rng.randbytes(32), 123456, 20, 0)
        prefix = header.mining_prefix()
        midstate = sha256_ref.header_midstate(prefix)
        tail = sha256_ref.header_tail_words(prefix)
        # Manually finish: chunk2 = tail words + nonce + padding for 80 bytes.
        nonce = 0xCAFEBABE
        chunk2 = struct.pack(">4I", *tail, nonce) + sha256_ref.padding(80)[0:48]
        assert len(chunk2) == 64
        state1 = sha256_ref.compress(midstate, chunk2)
        digest1 = struct.pack(">8I", *state1)
        assert digest1 == hashlib.sha256(header.with_nonce(nonce).serialize()).digest()


class TestRegistry:
    def test_known_backends_present(self):
        names = set(available_backends())
        assert {"cpu", "numpy"} <= names

    def test_get_backend_memoizes(self):
        assert get_backend("cpu") is get_backend("cpu")

    def test_unknown_backend_raises(self):
        with pytest.raises(KeyError):
            get_backend("definitely-not-a-backend")

    def test_lazy_resolve_in_fresh_process(self):
        # get_backend must work when the lazy loader (not a direct import)
        # is what registers the backend — regression: @register popping the
        # lazy entry made _resolve's own cleanup KeyError.
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [
                sys.executable,
                "-c",
                "import jax; jax.config.update('jax_platforms', 'cpu');"
                "from p1_tpu.hashx import get_backend;"
                "get_backend('jax'); print('resolved')",
            ],
            env=env,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert "resolved" in out.stdout

    def test_direct_import_does_not_double_list(self):
        # Importing a lazily-registered backend module directly fulfills the
        # lazy entry; the name must appear exactly once afterwards.
        import p1_tpu.hashx.jax_backend  # noqa: F401

        names = list(available_backends())
        assert names.count("jax") == 1
        assert len(names) == len(set(names))


def _random_prefix(seed: int) -> bytes:
    rng = random.Random(seed)
    header = BlockHeader(1, rng.randbytes(32), rng.randbytes(32), 1735689700, 8, 0)
    return header.mining_prefix()


class TestNumpyLanes:
    def test_lanes_match_reference_digests(self):
        prefix = _random_prefix(2)
        midstate = np.array(sha256_ref.header_midstate(prefix), dtype=np.uint32)
        tail = np.array(sha256_ref.header_tail_words(prefix), dtype=np.uint32)
        nonces = np.array([0, 1, 12345, 0xFFFFFFFF, 0x80000000], dtype=np.uint32)
        words = sha256d_lanes(midstate, tail, nonces)
        for lane, nonce in enumerate(nonces):
            header80 = prefix + struct.pack(">I", int(nonce))
            expect = sha256_ref.sha256d(header80)
            got = struct.pack(">8I", *(int(w[lane]) for w in words))
            assert got == expect, f"lane {lane} nonce {nonce:#x}"

    def test_target_mask_matches_host_check(self):
        prefix = _random_prefix(3)
        midstate = np.array(sha256_ref.header_midstate(prefix), dtype=np.uint32)
        tail = np.array(sha256_ref.header_tail_words(prefix), dtype=np.uint32)
        nonces = np.arange(4096, dtype=np.uint32)
        words = sha256d_lanes(midstate, tail, nonces)
        for difficulty in (4, 8, 12):
            mask = lanes_below_target(words, difficulty)
            for lane in np.flatnonzero(mask)[:4]:
                header80 = prefix + struct.pack(">I", int(nonces[lane]))
                assert meets_target(sha256_ref.sha256d(header80), difficulty)
            # spot-check some negatives too
            for lane in np.flatnonzero(~mask)[:4]:
                header80 = prefix + struct.pack(">I", int(nonces[lane]))
                assert not meets_target(sha256_ref.sha256d(header80), difficulty)


SEARCH_BACKENDS = ["cpu", "numpy"]
if os.environ.get("P1_TEST_NATIVE"):
    SEARCH_BACKENDS.append("native")


class TestSearchParity:
    """All backends agree on earliest-hit semantics."""

    @pytest.mark.parametrize("name", SEARCH_BACKENDS)
    def test_finds_known_hit(self, name):
        backend = get_backend(name)
        prefix = _random_prefix(4)
        # Find ground truth with the cpu reference first at tiny difficulty.
        truth = get_backend("cpu").search(prefix, 0, 4096, 8)
        assert truth.nonce is not None
        got = backend.search(prefix, 0, 4096, 8)
        assert got.nonce == truth.nonce

    @pytest.mark.parametrize("name", SEARCH_BACKENDS)
    def test_no_hit_returns_none(self, name):
        backend = get_backend(name)
        prefix = _random_prefix(5)
        res = backend.search(prefix, 0, 64, 255)
        assert res.nonce is None
        assert res.hashes_done == 64

    @pytest.mark.parametrize("name", SEARCH_BACKENDS)
    def test_respects_nonce_start(self, name):
        backend = get_backend(name)
        prefix = _random_prefix(6)
        truth = get_backend("cpu").search(prefix, 0, 1 << 14, 10)
        assert truth.nonce is not None
        # Start the search just past the first hit; must find a later one or none,
        # never the earlier nonce.
        later = backend.search(prefix, truth.nonce + 1, 1 << 14, 10)
        assert later.nonce is None or later.nonce > truth.nonce

    @pytest.mark.parametrize("name", SEARCH_BACKENDS)
    def test_search_hit_meets_target(self, name):
        backend = get_backend(name)
        prefix = _random_prefix(7)
        res = backend.search(prefix, 0, 1 << 14, 10)
        if res.nonce is not None:
            header80 = prefix + struct.pack(">I", res.nonce)
            assert meets_target(sha256_ref.sha256d(header80), 10)

    @pytest.mark.parametrize("name", SEARCH_BACKENDS)
    def test_arg_validation(self, name):
        backend = get_backend(name)
        with pytest.raises(ValueError):
            backend.search(b"x" * 75, 0, 10, 8)
        with pytest.raises(ValueError):
            backend.search(b"x" * 76, 0xFFFFFFFF, 2, 8)
