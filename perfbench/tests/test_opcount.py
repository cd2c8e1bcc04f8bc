"""The roofline's numerator: the spec op count of one SHA-256d candidate
equals the operations a plain FIPS 180-4 digest performs, and that digest
is hashlib's."""

import hashlib
import random
import struct

import pytest

from perfbench import opcount, sha256


def _sha256d(data: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_counted_digest_is_hashlib_and_counts_the_spec(seed):
    rng = random.Random(seed)
    header76 = rng.randbytes(76)
    nonce = rng.getrandbits(32)
    ops = sha256.Ops()
    digest, _ = sha256.candidate(header76, nonce, sha256.target_words(20), ops)
    assert digest == _sha256d(header76 + struct.pack(">I", nonce))
    assert ops.n == opcount.sha256d_candidate_ops() == 4621


@pytest.mark.parametrize("d", [0, 1, 8, 31, 32, 33, 200, 255])
def test_target_compare_matches_integer_compare(d):
    rng = random.Random(d)
    for _ in range(200):
        header76, nonce = rng.randbytes(76), rng.getrandbits(32)
        digest, ok = sha256.candidate(header76, nonce, sha256.target_words(d))
        want = d == 0 or int.from_bytes(digest, "big") < (1 << (256 - d))
        assert ok == want


@pytest.mark.parametrize("seed", [0, 1])
def test_counted_header_check_counts_the_spec(seed):
    rng = random.Random(seed)
    parent = rng.randbytes(80)
    prev = _sha256d(parent)
    header = rng.randbytes(4) + prev + rng.randbytes(44)
    words = struct.unpack(">8I", prev)
    for target, want in ((sha256.target_words(0), True), (sha256.target_words(255), False)):
        ops = sha256.Ops()
        assert sha256.header_check(header, words, target, ops) is want
        assert ops.n == opcount.header_verify_ops() == 6932
    assert not sha256.header_check(header, words[:7] + (words[7] ^ 1,), sha256.target_words(0))
