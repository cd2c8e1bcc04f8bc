"""Chain: validation, fork choice with reorg, orphans, replay, persistence."""

import pytest

from p1_tpu.chain import (
    AddStatus,
    Chain,
    ChainStore,
    ValidationError,
    check_block,
    generate_headers,
    replay_device,
    replay_host,
    save_chain,
)
from txutil import account, stx

from p1_tpu.core import Block, BlockHeader, Transaction, make_genesis, merkle_root
from p1_tpu.hashx import get_backend
from p1_tpu.miner import Miner

DIFF = 8  # cheap enough to mine dozens of blocks with hashlib
_MINER = Miner(backend=get_backend("cpu"))


def _mine_child(parent: Block, txs=(), ts_offset: int = 1, version: int = 1) -> Block:
    """Seal a valid child block of ``parent``."""
    header = BlockHeader(
        version=version,
        prev_hash=parent.block_hash(),
        merkle_root=merkle_root([tx.txid() for tx in txs]),
        timestamp=parent.header.timestamp + ts_offset,
        difficulty=parent.header.difficulty,
        nonce=0,
    )
    sealed = _MINER.search_nonce(header)
    assert sealed is not None
    return Block(sealed, tuple(txs))


@pytest.fixture(scope="module")
def chain_blocks():
    """Genesis + 3 mined main-chain blocks + a 5-block competing fork off
    genesis (mined once per module; chain state is rebuilt per test)."""
    genesis = make_genesis(DIFF)
    main = [genesis]
    for _ in range(3):
        main.append(_mine_child(main[-1]))
    fork = [genesis]
    for _ in range(5):
        # version=2 differentiates fork headers from main ones at h+1
        fork.append(_mine_child(fork[-1], version=2))
    return main, fork


class TestValidate:
    def test_valid_block_passes(self, chain_blocks):
        main, _ = chain_blocks
        check_block(main[1], DIFF)

    def test_wrong_difficulty(self, chain_blocks):
        main, _ = chain_blocks
        with pytest.raises(ValidationError, match="difficulty"):
            check_block(main[1], DIFF + 1)

    def test_bad_pow(self):
        genesis = make_genesis(DIFF)
        header = BlockHeader(
            1, genesis.block_hash(), bytes(32), genesis.header.timestamp + 1, DIFF, 0
        )
        # nonce 0 is (with overwhelming odds for this fixed header) not a hit
        from p1_tpu.core import meets_target

        assert not meets_target(header.block_hash(), DIFF)
        with pytest.raises(ValidationError, match="proof of work"):
            check_block(Block(header, ()), DIFF)

    def test_merkle_mismatch(self, chain_blocks):
        main, _ = chain_blocks
        tx = Transaction("a", "b", 1, 0, 0)
        forged = Block(main[1].header, (tx,))
        with pytest.raises(ValidationError, match="merkle"):
            check_block(forged, DIFF)

    def test_duplicate_txid_rejected(self):
        # CVE-2012-2459: [t1, t2, t3, t3] shares a merkle root with
        # [t1, t2, t3] (odd tail duplicated) -- the duplicate form must be
        # rejected even though the root matches.
        genesis = make_genesis(DIFF)
        t1 = Transaction("a", "b", 1, 0, 0)
        t2 = Transaction("c", "d", 2, 0, 0)
        t3 = Transaction("e", "f", 3, 0, 0)
        dup = (t1, t2, t3, t3)
        assert merkle_root([t.txid() for t in dup]) == merkle_root(
            [t.txid() for t in (t1, t2, t3)]
        )
        block = _mine_child(genesis, txs=dup)
        with pytest.raises(ValidationError, match="duplicate txid"):
            check_block(block, DIFF)

    def test_genesis_pow_waived(self):
        check_block(make_genesis(DIFF), DIFF, is_genesis=True)

    def test_coinbase_first_ok(self):
        genesis = make_genesis(DIFF)
        cb = Transaction.coinbase("miner-a", 1)
        tx = stx("a", "b", 1, 0, 0)
        check_block(_mine_child(genesis, txs=(cb, tx)), DIFF)

    def test_coinbase_not_first_rejected(self):
        genesis = make_genesis(DIFF)
        cb = Transaction.coinbase("miner-a", 1)
        tx = stx("a", "b", 1, 0, 0)
        block = _mine_child(genesis, txs=(tx, cb))
        with pytest.raises(ValidationError, match="coinbase"):
            check_block(block, DIFF)

    def test_coinbase_wrong_subsidy_rejected(self):
        # ADVICE r3 (medium): a hostile miner must not mint an arbitrary
        # reward — the coinbase amount is consensus-fixed.
        genesis = make_genesis(DIFF)
        cb = Transaction.coinbase("miner-a", 1, reward=10_000)
        block = _mine_child(genesis, txs=(cb,))
        with pytest.raises(ValidationError, match="subsidy"):
            check_block(block, DIFF)

    def test_unsigned_transfer_rejected(self):
        import dataclasses

        from p1_tpu.core.genesis import genesis_hash

        genesis = make_genesis(DIFF)
        # Right chain tag, no proof at all: the signature check must fire.
        naked = dataclasses.replace(
            Transaction("a", "b", 1, 0, 0), chain=genesis_hash(DIFF)
        )
        block = _mine_child(genesis, txs=(naked,))
        with pytest.raises(ValidationError, match="signature"):
            check_block(block, DIFF)

    def test_untagged_transfer_rejected(self):
        # A tx with no chain binding (or any foreign tag) is refused even
        # if its signature is internally valid — cross-chain replays die
        # here.
        from txutil import key_for

        genesis = make_genesis(DIFF)
        untagged = Transaction.transfer(key_for("a"), "b", 1, 0, 0)  # chain=b""
        block = _mine_child(genesis, txs=(untagged,))
        with pytest.raises(ValidationError, match="different chain"):
            check_block(block, DIFF)

    def test_cross_chain_replay_rejected(self):
        # A spend validly signed for the difficulty-12 chain, replayed
        # byte-identically on the difficulty-8 chain: rejected by tag.
        genesis = make_genesis(DIFF)
        foreign = stx("a", "b", 1, 0, 0, difficulty=12)
        assert foreign.verify_signature()  # internally valid...
        block = _mine_child(genesis, txs=(foreign,))
        with pytest.raises(ValidationError, match="different chain"):
            check_block(block, DIFF)  # ...but not for THIS chain

    def test_forged_sender_rejected(self):
        # mallory signs with HER key but claims alice's account as sender:
        # the fingerprint check must catch the mismatch.
        import dataclasses

        from txutil import account, key_for
        from p1_tpu.core.genesis import genesis_hash

        genesis = make_genesis(DIFF)
        mallory = key_for("mallory")
        theft = Transaction(
            account("alice"), mallory.account, 1, 0, 0, chain=genesis_hash(DIFF)
        )
        theft = dataclasses.replace(
            theft, pubkey=mallory.pubkey, sig=mallory.sign(theft.signing_bytes())
        )
        block = _mine_child(genesis, txs=(theft,))
        with pytest.raises(ValidationError, match="signature"):
            check_block(block, DIFF)

    def test_tampered_amount_rejected(self):
        # A validly signed tx whose amount is bumped after signing.
        import dataclasses

        genesis = make_genesis(DIFF)
        tampered = dataclasses.replace(stx("a", "b", 1, 0, 0), amount=40)
        block = _mine_child(genesis, txs=(tampered,))
        with pytest.raises(ValidationError, match="signature"):
            check_block(block, DIFF)

    def test_signed_coinbase_rejected(self):
        # Coinbases are minted by consensus, not spent by an owner — one
        # carrying key material is malformed.
        import dataclasses

        from txutil import key_for

        genesis = make_genesis(DIFF)
        key = key_for("miner")
        cb = Transaction.coinbase("miner-a", 1)
        cb = dataclasses.replace(
            cb, pubkey=key.pubkey, sig=key.sign(cb.signing_bytes())
        )
        block = _mine_child(genesis, txs=(cb,))
        with pytest.raises(ValidationError, match="unsigned"):
            check_block(block, DIFF)

    def test_two_coinbases_rejected(self):
        genesis = make_genesis(DIFF)
        cb1 = Transaction.coinbase("miner-a", 1)
        cb2 = Transaction.coinbase("miner-b", 1)
        block = _mine_child(genesis, txs=(cb1, cb2))
        with pytest.raises(ValidationError, match="coinbase"):
            check_block(block, DIFF)


class TestOrphanPool:
    """A hostile peer flooding unconnectable blocks must not grow memory:
    orphans need their own valid PoW to park, the pool is FIFO-capped, and
    re-received orphans are not double-parked."""

    def test_flood_is_bounded_and_chain_still_extends(self):
        import os

        from p1_tpu.chain.chain import MAX_ORPHANS

        diff = 2  # ~4 hashes per orphan: 10k mined orphans stay cheap
        chain = Chain(diff)
        miner = Miner(backend=get_backend("cpu"))
        for i in range(10_000):
            header = BlockHeader(1, os.urandom(32), bytes(32), i + 1, diff, 0)
            sealed = miner.search_nonce(header)
            assert sealed is not None
            res = chain.add_block(Block(sealed, ()))
            assert res.status is AddStatus.ORPHAN
        assert len(chain._orphan_hashes) <= MAX_ORPHANS
        assert len(chain._orphan_fifo) <= MAX_ORPHANS
        assert sum(len(v) for v in chain._orphans.values()) <= MAX_ORPHANS
        # the chain is unharmed: a legitimate child still connects
        child = _mine_child(chain.genesis)
        assert chain.add_block(child).status is AddStatus.ACCEPTED
        assert chain.height == 1

    def test_orphan_without_pow_rejected_not_parked(self):
        import os

        chain = Chain(20)
        header = BlockHeader(1, os.urandom(32), bytes(32), 1, 20, 0)
        res = chain.add_block(Block(header, ()))  # nonce 0: no PoW at d20
        assert res.status is AddStatus.REJECTED
        assert not chain._orphan_hashes

    def test_reparked_orphan_not_duplicated(self):
        import os

        diff = 2
        chain = Chain(diff)
        miner = Miner(backend=get_backend("cpu"))
        header = BlockHeader(1, os.urandom(32), bytes(32), 1, diff, 0)
        sealed = miner.search_nonce(header)
        orphan = Block(sealed, ())
        assert chain.add_block(orphan).status is AddStatus.ORPHAN
        res = chain.add_block(orphan)
        assert res.status is AddStatus.ORPHAN and res.reason == "already parked"
        assert len(chain._orphan_hashes) == 1
        assert sum(len(v) for v in chain._orphans.values()) == 1


class TestForkChoice:
    def test_linear_growth(self, chain_blocks):
        main, _ = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        for block in main[1:]:
            res = chain.add_block(block)
            assert res.status is AddStatus.ACCEPTED
            assert res.added == (block,)
            assert res.removed == ()
        assert chain.height == 3
        assert chain.tip == main[3]
        assert list(chain.main_chain()) == main

    def test_duplicate(self, chain_blocks):
        main, _ = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        chain.add_block(main[1])
        assert chain.add_block(main[1]).status is AddStatus.DUPLICATE

    def test_invalid_rejected(self, chain_blocks):
        main, _ = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        bad = Block(main[1].header, (Transaction("a", "b", 1, 0, 0),))
        res = chain.add_block(bad)
        assert res.status is AddStatus.REJECTED
        assert "merkle" in res.reason

    def test_shorter_fork_does_not_move_tip(self, chain_blocks):
        main, fork = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        for block in main[1:]:
            chain.add_block(block)
        res = chain.add_block(fork[1])  # height 1 vs tip height 3
        assert res.status is AddStatus.ACCEPTED
        assert not res.tip_changed
        assert chain.tip == main[3]

    def test_reorg_to_heavier_fork(self, chain_blocks):
        main, fork = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        for block in main[1:]:
            chain.add_block(block)
        # Strictly lighter fork blocks never move the tip.
        for block in fork[1:3]:
            assert not chain.add_block(block).tip_changed
        # Equal work at height 3: deterministic tie-break by smaller hash.
        chain.add_block(fork[3])
        expected_at_tie = min(main[3], fork[3], key=lambda b: b.block_hash())
        assert chain.tip == expected_at_tie
        # fork[4] is strictly heavier: tip must be fork[4] on every node.
        res = chain.add_block(fork[4])
        assert chain.tip == fork[4]
        assert chain.height == 4
        if expected_at_tie is main[3]:  # the reorg happened just now
            assert res.removed == tuple(reversed(main[1:]))
            assert res.added == tuple(fork[1:5])

    def test_equal_work_tiebreak_is_order_independent(self, chain_blocks):
        # Two nodes seeing the same blocks in different orders must agree.
        main, fork = chain_blocks
        a = Chain(DIFF, genesis=main[0])
        b = Chain(DIFF, genesis=main[0])
        blocks = main[1:4] + fork[1:4]
        for block in blocks:
            a.add_block(block)
        for block in reversed(blocks):
            b.add_block(block)
        assert a.tip_hash == b.tip_hash

    def test_connected_reports_cascaded_orphans(self, chain_blocks):
        # Persistence appends res.connected; it must include orphans the
        # triggering block unblocked, or restarts lose the chain suffix.
        main, _ = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        chain.add_block(main[2])  # orphan
        chain.add_block(main[3])  # orphan
        res = chain.add_block(main[1])
        assert res.connected == (main[1], main[2], main[3])
        plain = chain.add_block(_mine_child(main[3], ts_offset=99))
        assert len(plain.connected) == 1

    def test_orphan_then_connect(self, chain_blocks):
        main, _ = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        assert chain.add_block(main[2]).status is AddStatus.ORPHAN
        assert chain.add_block(main[3]).status is AddStatus.ORPHAN
        assert chain.height == 0
        res = chain.add_block(main[1])  # parent arrives: cascade connects
        assert res.status is AddStatus.ACCEPTED
        assert chain.height == 3
        assert res.added == tuple(main[1:])
        assert chain.tip == main[3]

    def test_locator_and_blocks_after(self, chain_blocks):
        main, _ = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        for block in main[1:]:
            chain.add_block(block)
        loc = chain.locator()
        assert loc[0] == chain.tip_hash
        assert loc[-1] == main[0].block_hash()
        peer = Chain(DIFF, genesis=main[0])
        missing = chain.blocks_after(peer.locator())
        assert missing == main[1:]


class TestReplay:
    @pytest.fixture(scope="class")
    def headers(self):
        return generate_headers(64, DIFF)

    def test_host_replay_valid(self, headers):
        report = replay_host(headers)
        assert report.valid and report.first_invalid is None
        assert report.n_headers == 64

    def test_device_replay_valid(self, headers):
        report = replay_device(headers, segment=16)
        assert report.valid, f"first invalid: {report.first_invalid}"

    def test_native_replay_matches_host(self, headers):
        # The C++ engine (config 3's native tier) agrees with the hashlib
        # oracle on a valid chain AND on the exact first-invalid index.
        from p1_tpu.chain import replay_native

        report = replay_native(headers)
        assert report.valid and report.first_invalid is None
        bad = list(headers)
        bad[37] = bad[37].with_nonce(bad[37].nonce ^ 1)
        host, native = replay_host(bad), replay_native(bad)
        assert not native.valid
        assert native.first_invalid == host.first_invalid == 37
        # Wrong difficulty field mid-chain is also caught (host parity).
        import dataclasses as dc

        bad2 = list(headers)
        bad2[30] = dc.replace(bad2[30], difficulty=DIFF + 1)
        assert (
            replay_native(bad2).first_invalid
            == replay_host(bad2).first_invalid
            == 30
        )

    def test_device_matches_host_on_corruption(self, headers):
        # Corrupt one nonce mid-chain: both paths must flag that index
        # (PoW breaks there, and linkage breaks at the next header).
        bad = list(headers)
        bad[37] = bad[37].with_nonce(bad[37].nonce ^ 1)
        host = replay_host(bad)
        device = replay_device(bad, segment=16)
        assert not host.valid and not device.valid
        assert host.first_invalid == device.first_invalid == 37

    def test_device_flags_broken_link(self, headers):
        bad = list(headers)
        # Re-mine header 21 onto the wrong parent (height 19's hash).
        draft = bad[21].with_nonce(0)
        import dataclasses as dc

        draft = dc.replace(draft, prev_hash=bad[19].block_hash())
        sealed = _MINER.search_nonce(draft)
        bad[21] = sealed
        host = replay_host(bad)
        device = replay_device(bad, segment=16)
        assert host.first_invalid == device.first_invalid == 21

    @pytest.mark.parametrize("kind", ["parsed", "fresh", "mixed", "nul_tail"])
    def test_pack_header_rows_matches_pack_headers(self, headers, kind):
        # Parsed headers carry their cached encoding; fresh ones (no
        # ``_raw``) take the serializing fallback; a nonce-0 header's
        # encoding ends in NUL bytes, which the fixed-width gather keeps.
        import dataclasses as dc

        import numpy as np

        from p1_tpu.chain import pack_header_rows, pack_headers, parse_headers

        parsed = parse_headers(b"".join(h.serialize() for h in headers))
        fresh = [dc.replace(h) for h in headers]
        chain = {
            "parsed": parsed,
            "fresh": fresh,
            "mixed": [p if i % 3 else f for i, (p, f) in enumerate(zip(parsed, fresh))],
            "nul_tail": parse_headers(
                b"".join(h.with_nonce(n).serialize() for h, n in zip(headers, (0, 256) * 4))
            ),
        }[kind]
        cached = [hasattr(h, "_raw") for h in chain]
        assert {"fresh": not any(cached), "mixed": 0 < sum(cached) < len(chain)}.get(
            kind, all(cached)
        )
        rows = pack_header_rows(chain)
        assert rows.shape == (len(chain), 20) and rows.dtype == np.dtype("<u4")
        assert rows.flags.c_contiguous
        assert rows.tobytes() == pack_headers(chain)
        if kind == "nul_tail":
            assert all(h.serialize().endswith(b"\0") for h in chain)

    @pytest.mark.parametrize("rest", ["parsed", "fresh"])
    def test_device_matches_host_on_a_deserialized_corruption(self, headers, rest):
        # As the benchmark's ``compare`` does: one mid-chain header object
        # swapped for ``BlockHeader.deserialize`` of its flipped bytes, the
        # rest cached (parsed) or not yet encoded (the gather's fallback).
        import dataclasses as dc

        from p1_tpu.chain import parse_headers

        if rest == "parsed":
            chain = parse_headers(b"".join(h.serialize() for h in headers))
        else:
            chain = [dc.replace(h) for h in headers]
        raw = bytearray(headers[37].serialize())
        raw[4 + 9] ^= 0x10  # a prev_hash bit
        chain[37] = BlockHeader.deserialize(bytes(raw))
        host = replay_host(chain)
        device = replay_device(chain, segment=24)
        assert host.first_invalid == device.first_invalid == 37

    def test_partial_segment_padding(self, headers):
        # 64 headers with segment 24 -> final segment is 16 real + 8 pad.
        report = replay_device(headers, segment=24)
        assert report.valid

    def test_difficulty_field_corruption_flagged_by_both(self, headers):
        # A difficulty-0 field makes any hash "meet target" -- both paths
        # must still flag it (the declared difficulty is consensus data).
        import dataclasses as dc

        bad = list(headers)
        bad[41] = dc.replace(bad[41], difficulty=0)
        host = replay_host(bad)
        device = replay_device(bad, segment=16)
        assert host.first_invalid == device.first_invalid == 41

    @pytest.mark.parametrize(
        "n, flip",
        [
            (1, None),  # one header
            (10, None),  # n < segment: no full segment, the tail alone
            (32, None),  # an exact multiple: no tail
            (40, None),  # two full segments and a partial tail
            (10, (9, 76, 0)),  # the tail alone, its last header's nonce
            (40, (35, 76, 5)),  # in the tail segment, a nonce bit
            (40, (39, 4, 3)),  # the last real header, a prev_hash bit
            (40, (27, 20, 7)),  # in the last full segment, a prev_hash bit
            (32, (31, 79, 1)),  # the last header of the last full segment
        ],
        ids=[
            "one_header",
            "under_one_segment",
            "exact_multiple",
            "partial_tail",
            "flip_under_one_segment",
            "flip_in_tail",
            "flip_in_last_real_header",
            "flip_in_last_full_segment",
            "flip_in_last_full_segment_no_tail",
        ],
    )
    def test_device_matches_host_at_every_length(self, headers, n, flip):
        # Segment 16: the body's full segments and the padded tail are
        # uploaded apart and joined on the device; a single-bit flip
        # (index, byte offset, bit) lands on either side of the join.
        chain = list(headers[:n])
        if flip is not None:
            index, offset, bit = flip
            raw = bytearray(chain[index].serialize())
            raw[offset] ^= 1 << bit
            chain[index] = BlockHeader.deserialize(bytes(raw))
        host = replay_host(chain)
        device = replay_device(chain, segment=16)
        assert (device.valid, device.first_invalid) == (
            host.valid,
            host.first_invalid,
        )
        assert host.valid == (flip is None)
        assert device.n_headers == n

    def test_device_scan_gets_big_endian_padded_words(self, headers, monkeypatch):
        # The contract the benchmark's control and fault tests patch into:
        # the scan step built by ``jax_sha256.jit_verify_chain_scan`` (looked
        # up at call time) gets u32[S, segment, 20] header words, big-endian,
        # the pad rows byte-copies of the last real header.
        import jax
        import numpy as np

        from p1_tpu.chain import pack_headers
        from p1_tpu.hashx import jax_sha256

        build, seen = jax_sha256.jit_verify_chain_scan, []

        def spying(n_segments, segment, platform=None, unroll=None):
            step = build(n_segments, segment, platform, unroll)

            def spy(words3, target, difficulty):
                seen.append(np.asarray(words3))
                return step(words3, target, difficulty)

            return spy

        monkeypatch.setattr(jax_sha256, "jit_verify_chain_scan", spying)
        chain = headers[:40]
        assert replay_device(chain, segment=16).valid
        (words3,) = seen
        assert words3.shape == (3, 16, 20) and words3.dtype == np.uint32
        rows = words3.reshape(-1, 20)
        np.testing.assert_array_equal(
            rows[:40], np.frombuffer(pack_headers(chain), ">u4").reshape(40, 20)
        )
        assert (rows[40:] == rows[39]).all()

        # Headers with no cached encoding (the gather's fallback) give the
        # scan the same words.
        import dataclasses as dc

        assert replay_device([dc.replace(h) for h in chain], segment=16).valid
        np.testing.assert_array_equal(seen[1], words3)

        # Another length with the same segment count compiles nothing new.
        compiles = []

        def count(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(secs)

        jax.monitoring.register_event_duration_secs_listener(count)
        try:
            assert replay_device(headers[:36], segment=16).valid
        finally:
            jax.monitoring.unregister_event_duration_listener(count)
        assert len(seen) == 3 and seen[2].shape == (3, 16, 20)
        assert compiles == []


class TestPersistence:
    def test_roundtrip(self, chain_blocks, tmp_path):
        main, fork = chain_blocks
        store = ChainStore(tmp_path / "chain.dat")
        chain = Chain(DIFF, genesis=main[0])
        for block in main[1:] + fork[1:]:
            res = chain.add_block(block)
            if res.status is AddStatus.ACCEPTED:
                store.append(block)
        store.close()

        resumed = ChainStore(tmp_path / "chain.dat").load_chain(DIFF)
        assert resumed.tip_hash == chain.tip_hash
        assert resumed.height == chain.height
        assert len(resumed) == len(chain)  # side branches survive too

    def test_truncated_tail_recovers(self, chain_blocks, tmp_path):
        main, _ = chain_blocks
        path = tmp_path / "chain.dat"
        store = ChainStore(path)
        for block in main[1:]:
            store.append(block)
        store.close()
        raw = path.read_bytes()
        path.write_bytes(raw[:-7])  # crash mid-append
        resumed = ChainStore(path).load_chain(DIFF)
        assert resumed.height == 2  # last whole record survives

    def test_append_after_truncated_tail(self, chain_blocks, tmp_path):
        # Appending to a store with a garbage partial tail must first drop
        # the tail, or its stale length prefix poisons every later load.
        main, _ = chain_blocks
        path = tmp_path / "chain.dat"
        store = ChainStore(path)
        store.append(main[1])
        store.append(main[2])
        store.close()
        path.write_bytes(path.read_bytes()[:-7])  # crash mid-append of [2]
        store = ChainStore(path)
        store.append(main[2])
        store.append(main[3])
        store.close()
        resumed = ChainStore(path).load_chain(DIFF)
        assert resumed.height == 3
        assert resumed.tip_hash == main[3].block_hash()

    def test_save_chain_snapshot(self, chain_blocks, tmp_path):
        main, _ = chain_blocks
        chain = Chain(DIFF, genesis=main[0])
        for block in main[1:]:
            chain.add_block(block)
        save_chain(chain, tmp_path / "snap.dat")
        resumed = ChainStore(tmp_path / "snap.dat").load_chain(DIFF)
        assert list(resumed.main_chain()) == main

    def test_wrong_magic(self, tmp_path):
        path = tmp_path / "junk.dat"
        path.write_bytes(b"not a chain store")
        with pytest.raises(ValueError, match="not a chain store"):
            ChainStore(path).load_blocks()

    def test_append_fsyncs_every_block(self, chain_blocks, tmp_path, monkeypatch):
        # Durability contract (VERDICT r3 item 6): an acknowledged append
        # must survive OS crash, so fsync runs once per append — and the
        # fsync=False escape hatch really skips it.
        import os as os_mod

        main, _ = chain_blocks
        calls = []
        real_fsync = os_mod.fsync
        monkeypatch.setattr(
            "p1_tpu.chain.store.os.fsync",
            lambda fd: (calls.append(fd), real_fsync(fd))[1],
        )
        store = ChainStore(tmp_path / "sync.dat")
        store.append(main[1])
        store.append(main[2])
        store.close()
        assert len(calls) == 2
        store = ChainStore(tmp_path / "nosync.dat", fsync=False)
        store.append(main[1])
        store.close()
        assert len(calls) == 2  # unchanged


class TestLedger:
    def test_balances_over_mined_chain(self):
        from p1_tpu.chain import balances

        genesis = make_genesis(DIFF)
        alice, bob = account("alice"), account("bob")
        cb1 = Transaction.coinbase(alice, 1)
        b1 = _mine_child(genesis, txs=(cb1,))
        # alice pays bob 20 (fee 2) in a block mined by carol.
        cb2 = Transaction.coinbase("carol", 2)
        pay = stx("alice", bob, 20, 2, 0)
        b2 = _mine_child(b1, txs=(cb2, pay))
        ledger = balances([genesis, b1, b2])
        assert ledger[alice] == 50 - 20 - 2
        assert ledger[bob] == 20
        assert ledger["carol"] == 50 + 2  # reward + fees
        assert sum(ledger.values()) == 100  # rewards minted, fees conserved
        # The audit view agrees with the consensus ledger on a real chain.
        chain = Chain(DIFF, genesis=genesis)
        assert chain.add_block(b1).status is AddStatus.ACCEPTED
        assert chain.add_block(b2).status is AddStatus.ACCEPTED
        assert chain.balances_snapshot() == {
            a: v for a, v in ledger.items() if v
        }

    def test_coinbase_less_block_burns_fees(self):
        # Pure-view property on a hypothetical block sequence: the view
        # never rejects (consensus would - alice is unfunded), and a
        # coinbase-less block's fees are credited to nobody.
        from p1_tpu.chain import balances

        genesis = make_genesis(DIFF)
        alice, bob = account("alice"), account("bob")
        pay = stx("alice", bob, 5, 3, 0)
        b1 = _mine_child(genesis, txs=(pay,))
        ledger = balances([genesis, b1])
        assert ledger[alice] == -8 and ledger[bob] == 5
        assert sum(ledger.values()) == -3  # the fee is burned

    def test_cli_balances_from_store(self, tmp_path):
        import json as json_mod
        import subprocess
        import sys

        from p1_tpu.chain import Chain, save_chain

        genesis = make_genesis(DIFF)
        chain = Chain(DIFF, genesis=genesis)
        alice = account("alice")
        cb = Transaction.coinbase(alice, 1)
        chain.add_block(_mine_child(genesis, txs=(cb,)))
        store = tmp_path / "chain.dat"
        save_chain(chain, store)
        proc = subprocess.run(
            [
                sys.executable, "-m", "p1_tpu", "balances",
                "--store", str(store), "--difficulty", str(DIFF),
                "--account", alice,
            ],
            capture_output=True,
            text=True,
            timeout=110,
            cwd="/root/repo",
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json_mod.loads(proc.stdout.strip())
        assert out["balance"] == 50 and out["height"] == 1


class TestCrashRecovery:
    def test_sigkill_mid_mining_then_restart(self, tmp_path):
        """Real fault injection (SURVEY §5): SIGKILL a mining node process
        and restart on the same store — the log must replay to a valid
        chain (possibly minus a torn tail record) and keep growing."""
        import json
        import os
        import signal
        import subprocess
        import sys
        import time

        store = tmp_path / "crash.dat"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        repo_root = str(__import__("pathlib").Path(__file__).resolve().parents[1])
        cmd = [
            sys.executable, "-m", "p1_tpu", "node",
            "--port", "0", "--difficulty", "10", "--backend", "cpu",
            "--store", str(store), "--duration", "60",
        ]
        err_path = tmp_path / "node.err"
        with open(err_path, "w") as err_fh:
            proc = subprocess.Popen(
                cmd, env=env, cwd=repo_root,
                stdout=subprocess.DEVNULL, stderr=err_fh,
            )
            try:
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if proc.poll() is not None:  # died at startup: fail fast
                        raise AssertionError(
                            f"node exited rc={proc.returncode}: "
                            f"{err_path.read_text()[-2000:]}"
                        )
                    if store.exists() and store.stat().st_size > 2000:
                        break
                    time.sleep(0.1)
                else:
                    raise AssertionError(
                        "node never persisted blocks: "
                        f"{err_path.read_text()[-2000:]}"
                    )
            finally:
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=30)

        # Restart on the possibly-torn store: it must resume and extend.
        out = subprocess.run(
            [
                sys.executable, "-m", "p1_tpu", "node",
                "--port", "0", "--difficulty", "10", "--backend", "cpu",
                "--store", str(store), "--duration", "2",
            ],
            env=env, cwd=repo_root,
            capture_output=True, text=True, timeout=110,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        status = json.loads(out.stdout.strip().splitlines()[-1])
        assert status["height"] > 0
        # And the final store must audit clean.
        resumed = ChainStore(store).load_chain(10)
        assert resumed.height >= status["height"] - 1

    def test_store_mutation_fuzz_fails_closed(self, chain_blocks, tmp_path):
        """Arbitrary corruption of a store must degrade, not explode, on
        BOTH paths the node restart uses: ``acquire()`` (which converts
        corruption to RuntimeError or truncates the torn tail under the
        lock) and ``load_chain`` (which re-validates every surviving
        record).  Whatever loads must be a prefix-consistent valid chain."""
        import random as rnd

        main, fork = chain_blocks
        path = tmp_path / "fuzz.dat"
        store = ChainStore(path)
        for block in main[1:] + fork[1:]:
            store.append(block)
        store.close()
        seed_bytes = path.read_bytes()
        seed_height = ChainStore(path).load_chain(DIFF).height

        rng = rnd.Random(11)
        for _ in range(300):
            buf = bytearray(seed_bytes)
            op = rng.randrange(3)
            if op == 0:
                buf = buf[: rng.randrange(len(buf))]
            elif op == 1:
                buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
            else:
                buf += bytes(rng.randrange(1, 16))
            path.write_bytes(bytes(buf))
            # Path 1: the node's restart sequence (lock + tail-truncate).
            writer = ChainStore(path)
            try:
                writer.acquire()
            except RuntimeError:
                writer.close()
                path.write_bytes(bytes(buf))  # undo any partial truncation
            else:
                writer.close()
            # Path 2: plain read-side load of whatever is on disk now.
            try:
                chain = ChainStore(path).load_chain(DIFF)
            except ValueError:
                continue  # fails closed
            # Whatever loaded must be internally consistent and no taller
            # than the uncorrupted original.
            assert chain.height <= seed_height
            assert len(list(chain.main_chain())) == chain.height + 1


class TestCompact:
    def test_cli_compact_drops_side_branches(self, tmp_path):
        import json as json_mod
        import subprocess
        import sys

        genesis = make_genesis(DIFF)
        main = [genesis]
        for _ in range(4):
            main.append(_mine_child(main[-1]))
        fork = _mine_child(genesis, version=2)  # loses fork choice
        store_path = tmp_path / "chain.dat"
        store = ChainStore(store_path)
        for block in [*main[1:3], fork, *main[3:]]:
            store.append(block)
        store.close()

        proc = subprocess.run(
            [
                sys.executable, "-m", "p1_tpu", "compact",
                "--store", str(store_path),
            ],
            capture_output=True,
            text=True,
            timeout=110,
            cwd="/root/repo",
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json_mod.loads(proc.stdout.strip())
        assert out["height"] == 4
        assert out["records_before"] == 5  # 4 main + 1 fork (no genesis rec)
        assert out["records_after"] == 5  # genesis + 4 main
        # The compacted store reloads to the same tip, fork gone.
        reloaded = ChainStore(store_path).load_chain(DIFF)
        assert reloaded.tip_hash == main[-1].block_hash()
        assert len(reloaded) == 5


    def test_compact_refuses_locked_store(self, tmp_path):
        import subprocess
        import sys

        genesis = make_genesis(DIFF)
        store_path = tmp_path / "live.dat"
        writer = ChainStore(store_path)
        writer.append(_mine_child(genesis))  # holds the writer flock
        try:
            proc = subprocess.run(
                [
                    sys.executable, "-m", "p1_tpu", "compact",
                    "--store", str(store_path),
                ],
                capture_output=True,
                text=True,
                timeout=110,
                cwd="/root/repo",
            )
            assert proc.returncode == 2
            assert "locked by another process" in proc.stderr
        finally:
            writer.close()

    def test_second_writer_refused(self, tmp_path):
        genesis = make_genesis(DIFF)
        store_path = tmp_path / "one_writer.dat"
        a = ChainStore(store_path)
        a.append(_mine_child(genesis))
        b = ChainStore(store_path)
        try:
            with pytest.raises(RuntimeError, match="locked"):
                b.append(_mine_child(genesis, ts_offset=2))
        finally:
            a.close()


class TestForkChoiceProperty:
    """Randomized property test (SURVEY §5): for ANY block DAG delivered in
    ANY order, every node converges to the same tip, and that tip is the
    brute-force best (max cumulative work, lexicographically smallest hash
    on ties).  Exercises orphan parking, cascaded connects, and reorgs far
    beyond the hand-written cases."""

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_random_dag_converges_to_brute_force_best(self, seed):
        import random as rnd

        rng = rnd.Random(seed)
        diff = 2
        genesis = make_genesis(diff)
        blocks = [genesis]
        heights = {genesis.block_hash(): 0}
        for i in range(60):
            parent = rng.choice(blocks)
            # Distinct sibling blocks via a unique coinbase-style tx.
            tx = Transaction("coinbase", f"m{seed}", 50, 0, i)
            child = _mine_child(parent, txs=(tx,), ts_offset=rng.randint(1, 9))
            blocks.append(child)
            heights[child.block_hash()] = heights[parent.block_hash()] + 1

        # Brute-force best: max height (fixed difficulty => work ~ height),
        # tie-break smallest hash.
        best_h = max(heights.values())
        expect_tip = min(
            b.block_hash() for b in blocks if heights[b.block_hash()] == best_h
        )

        non_genesis = blocks[1:]
        tips = set()
        for trial in range(3):
            order = non_genesis[:]
            rng.shuffle(order)
            chain = Chain(diff, genesis=genesis)
            for block in order:
                chain.add_block(block)
            assert chain.height == best_h
            # Every block must have connected despite arbitrary order.
            assert len(chain) == len(blocks)
            # The height index must agree with the tip walk.
            main = list(chain.main_chain())
            assert len(main) == best_h + 1
            assert main[-1].block_hash() == chain.tip_hash
            tips.add(chain.tip_hash)
        assert tips == {expect_tip}


class TestTrustedResume:
    """The fast-resume path (VERDICT r4 weak #3): a node reloading its
    OWN flocked store skips the stateless checks it already ran before
    appending; the rebuilt state must be IDENTICAL to a full
    revalidation — tip, every balance, every nonce, side branches."""

    def test_trusted_equals_full_validation(self, tmp_path):
        store_path = tmp_path / "chain.dat"
        chain = Chain(DIFF)
        store = ChainStore(store_path)
        alice = account("alice")
        # A dozen blocks: coinbases to alice, signed spends, one fork.
        for h in range(1, 9):
            tip = chain.tip
            txs = [Transaction.coinbase(alice, h)]
            if h > 2:
                txs.append(
                    stx("alice", account("bob"), 2, 1, h - 3, difficulty=DIFF)
                )
            header = BlockHeader(
                1,
                tip.block_hash(),
                merkle_root([t.txid() for t in txs]),
                tip.header.timestamp + 1,
                DIFF,
                0,
            )
            sealed = _MINER.search_nonce(header)
            res = chain.add_block(Block(sealed, tuple(txs)))
            assert res.status is AddStatus.ACCEPTED
            store.append(chain.tip)
        # A surviving side branch too.
        fork_parent = chain.get(chain.tip.header.prev_hash)
        side = Block(
            _MINER.search_nonce(
                BlockHeader(
                    1,
                    fork_parent.block_hash(),
                    merkle_root([Transaction.coinbase("m2", 8).txid()]),
                    fork_parent.header.timestamp + 2,
                    DIFF,
                    0,
                )
            ),
            (Transaction.coinbase("m2", 8),),
        )
        assert chain.add_block(side).status is AddStatus.ACCEPTED
        store.append(side)
        store.close()

        full = ChainStore(store_path).load_chain(DIFF)
        fast = ChainStore(store_path).load_chain(DIFF, trusted=True)
        assert fast.tip_hash == full.tip_hash == chain.tip_hash
        assert fast.height == full.height
        assert len(fast) == len(full) == len(chain)  # side branch kept
        assert fast.balances_snapshot() == full.balances_snapshot()
        assert fast.nonce(alice) == full.nonce(alice) == chain.nonce(alice)

    def test_trusted_still_enforces_contextual_rules(self, tmp_path):
        """Trust covers only what this node already checked; contextual
        linking still runs, so a record stream from a DIFFERENT chain
        cannot silently graft on (the none-connected guard fires)."""
        other = Chain(DIFF + 1)
        b = Block(
            _MINER.search_nonce(
                BlockHeader(
                    1,
                    other.genesis.block_hash(),
                    merkle_root([Transaction.coinbase("m", 1).txid()]),
                    other.genesis.header.timestamp + 1,
                    DIFF + 1,
                    0,
                )
            ),
            (Transaction.coinbase("m", 1),),
        )
        assert other.add_block(b).status is AddStatus.ACCEPTED
        path = tmp_path / "foreign.dat"
        store = ChainStore(path)
        store.append(b)
        store.close()
        with pytest.raises(ValueError, match="do not connect"):
            ChainStore(path).load_chain(DIFF, trusted=True)
