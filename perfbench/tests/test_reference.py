"""The reference's device scan finds the same first hit as ``hashlib``,
on one device and across four, stopping where it is told."""

import random
import struct

import jax
import pytest

from perfbench import reference


@pytest.mark.parametrize("n", [1, 4])
def test_first_hit_agrees_with_hashlib(n):
    rng = random.Random(n)
    scan = reference.FirstHitScan(jax.devices()[:n], 1 << 10, 1)
    for _ in range(20):
        header76, d = rng.randbytes(76), rng.choice([6, 9, 11])
        want = next((x for x in range(1 << 16)
                     if reference.pow_ok(header76 + struct.pack(">I", x), d)), None)
        stop = rng.randrange(1, 1 << 14)
        expect = want if want is not None and want < stop else None
        assert scan.first_hit(header76, d, stop) == expect


@pytest.fixture(scope="module")
def chain():
    from p1_tpu.chain.replay import generate_headers, pack_headers
    from p1_tpu.hashx import get_backend

    return pack_headers(generate_headers(300, 5, backend=get_backend("native")))


def _host(raw):
    from p1_tpu.chain.replay import parse_headers, replay_host

    return replay_host(parse_headers(raw)).first_invalid


def _flip(raw, pos, mask):
    out = bytearray(raw)
    out[pos] ^= mask
    return bytes(out)


def test_chain_reference_agrees_with_replay_host(chain):
    """The plain chain check and the program's hashlib replay name the
    same first invalid header, on the clean chain and on broken ones; a
    check from ``start`` agrees with the whole."""
    assert reference.chain_first_invalid(chain, 5) is None is _host(chain)
    rng = random.Random(7)
    for _ in range(40):
        i = rng.randrange(1, 300)
        pos = i * 80 + rng.choice([rng.randrange(4, 36), 75, rng.randrange(76, 80)])
        bad = _flip(chain, pos, 1 << rng.randrange(8))
        want = _host(bad)
        assert want in (i, i + 1, None)
        assert reference.chain_first_invalid(bad, 5) == want
        assert reference.chain_first_invalid(bad, 5, start=i) == want


def test_chain_reference_pins_genesis_and_difficulty(chain):
    # A flip of genesis' nonce: the program's replay waives genesis' work
    # and leaves its identity to the caller; the reference pins it.
    assert reference.chain_first_invalid(_flip(chain, 79, 1), 5) == 0
    assert reference.chain_first_invalid(chain, 6) == 0
    assert reference.chain_first_invalid(chain[:80], 5) is None
