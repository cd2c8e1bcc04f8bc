"""The spillable header plane: per-segment .hdrx indexes and the
archive serve-only boot (chain/headerplane.py, round 18).

What must hold: the plane is a pure cache of the segment bytes (byte-
identical headers, correct hash/txid lookups), and an ``ArchiveChain``
anchored on a snapshot serves header/balance/proof queries with only
the hot window materialized — including proofs for COLD transactions,
read back one record at a time from their segment.
"""

import pytest

from test_node import DIFF

from p1_tpu.chain import ChainStore, SegmentedStore, snapshot as snapmod
from p1_tpu.chain.headerplane import (
    ArchiveChain,
    HeaderPlane,
    SegmentIndex,
    write_segment_index,
)
from p1_tpu.chain.proof import verify_tx_proof
from p1_tpu.core.hashutil import sha256d
from p1_tpu.node.testing import make_blocks

SEG_BYTES = 600


@pytest.fixture(scope="module")
def blocks():
    return make_blocks(10, difficulty=DIFF)


def _linear_store(path, blocks, segment_bytes=SEG_BYTES):
    """A LINEAR segmented store: genesis at record 0, ordinal == height
    (the archive-serving shape)."""
    store = SegmentedStore(path, segment_bytes=segment_bytes)
    for h, block in enumerate(blocks):
        store.append(block, height=h)
    store.close()
    return store


def _snapshot_at(blocks, height, path):
    """A PR 9 snapshot file for the chain at ``height``."""
    from p1_tpu.chain.chain import Chain

    chain = Chain(DIFF)
    chain.checkpoint_interval = height
    for b in blocks[1:]:
        chain.add_block(b)
    h, block, balances, nonces, root = chain.snapshot_state()
    assert h == height
    manifest, chunks = snapmod.build_records(h, block, balances, nonces)
    snapmod.write_snapshot(path, manifest, chunks)
    return chain


class TestSegmentIndex:
    def test_round_trip(self, tmp_path, blocks):
        path = tmp_path / "chain.dat"
        _linear_store(path, blocks)
        seg_dir = path.with_name(path.name + ".d")
        seg0 = seg_dir / "seg00000.p1s"
        data = seg0.read_bytes()
        hx = tmp_path / "seg0.hdrx"
        n = write_segment_index(data, hx)
        idx = SegmentIndex(hx)
        assert idx.count == n > 0
        spans = ChainStore.scan(data).spans
        for ordinal, (off, length) in enumerate(spans):
            hdr = data[off : off + 80]
            assert idx.header_at(ordinal) == hdr
            assert idx.find_hash(sha256d(hdr)) == ordinal
            assert idx.record_span(ordinal) == (off, length)
        # Coinbase txids resolve to their record (genesis carries no
        # transactions — nothing of it lands in the txid index).
        for ordinal in range(n):
            block = blocks[ordinal]
            if block.txs:
                assert idx.find_txid(block.txs[0].txid()) == ordinal
        assert idx.find_hash(b"\x00" * 32) is None
        assert idx.find_txid(b"\xff" * 32) is None
        idx.close()

    def test_corrupt_index_refused(self, tmp_path, blocks):
        path = tmp_path / "chain.dat"
        _linear_store(path, blocks)
        seg0 = path.with_name(path.name + ".d") / "seg00000.p1s"
        hx = tmp_path / "bad.hdrx"
        write_segment_index(seg0.read_bytes(), hx)
        data = bytearray(hx.read_bytes())
        data[20] ^= 0x01
        hx.write_bytes(bytes(data))
        with pytest.raises(ValueError, match="CRC mismatch"):
            SegmentIndex(hx)


class TestHeaderPlane:
    def test_cumulative_ordinals(self, tmp_path, blocks):
        path = tmp_path / "chain.dat"
        store = _linear_store(path, blocks)
        seg_dir = path.with_name(path.name + ".d")
        indexes = []
        for seg in store.segments:
            hx = seg_dir / f"seg{seg.seg_id:05d}.hdrx"
            write_segment_index((seg_dir / seg.name).read_bytes(), hx)
            indexes.append(SegmentIndex(hx))
        plane = HeaderPlane(indexes)
        assert plane.count == len(blocks)
        for h, block in enumerate(blocks):
            assert plane.header_at(h) == block.header.serialize()
            assert plane.hash_at(h) == block.block_hash()
        assert plane.header_at(len(blocks)) is None
        hit = plane.find_txid(blocks[3].txs[0].txid())
        assert hit is not None and hit[0] == 3
        plane.close()


class TestArchiveChain:
    def test_boot_and_serve(self, tmp_path, blocks):
        path = tmp_path / "chain.dat"
        _linear_store(path, blocks)
        snap_path = tmp_path / "snap.p1s"
        full = _snapshot_at(blocks, 8, snap_path)
        arch = ArchiveChain(path, snap_path, DIFF)
        try:
            assert arch.base_height == 8
            assert arch.height == len(blocks) - 1
            # Headers: hot window above the base, plane below it.
            for h, block in enumerate(blocks):
                assert arch.header_bytes_at(h) == block.header.serialize()
                assert arch.hash_at(h) == block.block_hash()
            # Balances match the fully-replayed chain's ledger.
            for acct in full.balances_snapshot():
                assert arch.balance(acct) == full.balance(acct)
            # A COLD proof (below the base) is served from the plane +
            # one record read, and verifies end to end.
            cold_txid = blocks[2].txs[0].txid()
            proof = arch.tx_proof(cold_txid)
            assert proof is not None and proof.height == 2
            verify_tx_proof(
                proof, DIFF, blocks[0].block_hash(), txid=cold_txid
            )
            # A hot proof comes from the chain window.
            hot_txid = blocks[-1].txs[0].txid()
            hot = arch.tx_proof(hot_txid)
            assert hot is not None and hot.height == len(blocks) - 1
            assert arch.tx_proof(b"\x00" * 32) is None
            # The whole-archive PoW replay holds.
            report, count = arch.verify_headers()
            assert count == len(blocks) and report.valid
        finally:
            arch.close()

    def test_wrong_snapshot_refused(self, tmp_path, blocks):
        path = tmp_path / "chain.dat"
        _linear_store(path, blocks)
        other = make_blocks(9, difficulty=DIFF, miner_id="someone-else")
        snap_path = tmp_path / "wrong.p1s"
        _snapshot_at(other, 8, snap_path)
        with pytest.raises(ValueError, match="does not match"):
            ArchiveChain(path, snap_path, DIFF)

    def test_nonlinear_store_refused(self, tmp_path, blocks):
        """A node-style log (no genesis record) fails the linearity
        gate instead of serving wrong heights."""
        path = tmp_path / "chain.dat"
        store = SegmentedStore(path, segment_bytes=SEG_BYTES)
        # Records out of line: skip genesis AND drop a middle block.
        for h, block in enumerate(blocks[2:], start=2):
            store.append(block, height=h)
        store.close()
        snap_path = tmp_path / "snap.p1s"
        _snapshot_at(blocks, 8, snap_path)
        with pytest.raises(ValueError):
            ArchiveChain(path, snap_path, DIFF)

    def test_pruned_cold_bodies_refuse_proofs_keep_headers(
        self, tmp_path, blocks
    ):
        path = tmp_path / "chain.dat"
        _linear_store(path, blocks)
        snap_path = tmp_path / "snap.p1s"
        _snapshot_at(blocks, 8, snap_path)
        store = SegmentedStore(path)
        store.acquire()
        first = store.segments[0]
        assert store.prune_below(first.max_height + 1) >= 1
        store.close()
        arch = ArchiveChain(path, snap_path, DIFF)
        try:
            # Headers below the pruned floor still serve (the plane
            # survives the bodies)...
            for h in range(first.max_height + 1):
                assert arch.header_bytes_at(h) == blocks[h].header.serialize()
            # ...but proofs there honestly refuse.
            assert arch.tx_proof(blocks[1].txs[0].txid()) is None
        finally:
            arch.close()


@pytest.mark.slow
def test_archive_scale_acceptance_1m(tmp_path):
    """The acceptance property at tier-1-adjacent scale: a synthetic
    1M-block segmented archive boots in a FRESH process and serves
    header/balance/proof queries with peak RSS far under the 1 GB bar."""
    from archiveutil import bench_archive

    out = bench_archive(1_000_000, tmp_path / "arch")
    assert out["height"] == 999_999
    assert out["archive_boot_rss_mb"] < 1024, out
    assert out["archive_query_qps"] > 1_000
    assert out["archive_proof_qps"] > 100
    assert out["archive_resume_bps"] > 10_000
