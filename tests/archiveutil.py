"""A synthetic multi-million-block SEGMENTED archive, and its fresh-process
boot, for ``test_headerplane.py``'s archive-scale acceptance test.

The store is coinbase-only and LINEAR (genesis at record 0, ordinal ==
height — the compacted/archive shape), crafted at the BYTE level: one
template coinbase transaction whose seq field is patched per height,
headers packed directly, records appended through
``SegmentedStore.append_raw`` — no Block objects in the build loop.  The
first blocks are cross-checked byte-identical against the real object
serializer, so the synthetic store is exactly what a node would have
written.

The boot runs in a FRESH process (this file, ``--phase boot``) so that
its VmHWM is the archive's serving footprint, not the build's.
"""

from __future__ import annotations

import argparse
import json
import struct
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_U32 = struct.Struct(">I")
_HDR = struct.Struct(">I32s32sIII")
_MINER = "bench-miner"

#: Snapshot cadence for the synthetic archive: the boot's tail replay
#: is bounded by one interval, so keep it small relative to the store.
SNAP_INTERVAL = 4096
DIFFICULTY = 1
SEGMENT_BYTES = 16 << 20
#: Random-height header queries the boot serves (and up to 100 proofs).
QUERIES = 2000


def _tx_template(miner: str) -> tuple[bytearray, int]:
    """(mutable coinbase tx bytes, offset of the u64 seq field)."""
    from p1_tpu.core.tx import Transaction

    a = Transaction.coinbase(miner, 0).serialize()
    b = Transaction.coinbase(miner, 1).serialize()
    assert len(a) == len(b)
    # seq is the only differing field; it is a big-endian u64 ending at
    # the last differing byte.
    last_diff = max(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
    seq_off = last_diff - 7
    return bytearray(a), seq_off


def build_archive(store_path: Path, n_blocks: int) -> dict:
    """Craft the linear store + its snapshot sidecar; returns timings."""
    from hashlib import sha256

    from p1_tpu.chain import snapshot as snapmod
    from p1_tpu.chain.segstore import SegmentedStore
    from p1_tpu.core.block import Block
    from p1_tpu.core.genesis import make_genesis
    from p1_tpu.core.tx import BLOCK_REWARD, Transaction
    from p1_tpu.core.header import BlockHeader

    def sha256d(b: bytes) -> bytes:
        return sha256(sha256(b).digest()).digest()

    genesis = make_genesis(DIFFICULTY)
    tx, seq_off = _tx_template(_MINER)
    pack_seq = struct.Struct(">Q").pack_into
    base = (max(n_blocks - 1, 1) // SNAP_INTERVAL) * SNAP_INTERVAL
    anchor_payload: bytes | None = None
    store = SegmentedStore(
        store_path, fsync=False, segment_bytes=SEGMENT_BYTES
    )
    t0 = time.perf_counter()
    store.append_raw(genesis.serialize(), height=0)
    prev = genesis.block_hash()
    ts0 = genesis.header.timestamp
    tx_len_prefix = _U32.pack(1) + _U32.pack(len(tx))
    for h in range(1, n_blocks):
        pack_seq(tx, seq_off, h)
        txid = sha256d(tx)
        hdr = _HDR.pack(1, prev, txid, ts0 + h, DIFFICULTY, 0)
        payload = hdr + tx_len_prefix + bytes(tx)
        if h <= 3:
            # Self-check: crafted bytes are EXACTLY what the object
            # layer serializes — the synthetic store is real.
            real = Block(
                header=BlockHeader(1, prev, txid, ts0 + h, DIFFICULTY, 0),
                txs=[Transaction.coinbase(_MINER, h)],
            ).serialize()
            assert payload == real, "crafted record diverged from objects"
        store.append_raw(payload, height=h)
        if h == base:
            anchor_payload = payload
        prev = sha256d(hdr)
        if h % 262144 == 0:
            # The span dict is the only O(chain) term in the build loop;
            # the archive boot reindexes lazily from disk anyway.
            store._body_spans.clear()
    store.sync()
    build_s = time.perf_counter() - t0
    segments = len(store.segments)
    store.close()
    # The snapshot sidecar: the miner's whole subsidy stream at the
    # base height — byte-for-byte what chain.snapshot_state() packages
    # for this chain shape.
    assert anchor_payload is not None
    anchor = Block.deserialize(anchor_payload)
    balances = {_MINER: base * BLOCK_REWARD}
    manifest, chunks = snapmod.build_records(base, anchor, balances, {})
    snap_path = store_path.with_name(store_path.name + ".archsnap")
    snapmod.write_snapshot(snap_path, manifest, chunks)
    return {
        "build_s": round(build_s, 3),
        "archive_ingest_bps": round((n_blocks - 1) / build_s),
        "segments": segments,
        "snapshot_base": base,
    }


def measure_resume(store_path: Path) -> dict:
    """Whole-archive packed-header extraction rate (the full-scan
    resume/rebuild shape)."""
    from p1_tpu.chain.segstore import SegmentedStore

    store = SegmentedStore(store_path)
    t0 = time.perf_counter()
    raw, count = store.packed_headers()
    dt = time.perf_counter() - t0
    store.close()
    return {
        "archive_resume_bps": round(count / dt),
        "resume_records": count,
        "resume_s": round(dt, 3),
    }


def boot_phase(store_path: str) -> None:
    """Subprocess body: boot the archive, serve queries, report VmHWM."""
    import random

    from p1_tpu.chain.headerplane import ArchiveChain

    snap = store_path + ".archsnap"
    t0 = time.perf_counter()
    arch = ArchiveChain(store_path, snap, DIFFICULTY)
    boot_s = time.perf_counter() - t0
    rng = random.Random(18)
    height = arch.height
    # Header queries: random heights across the WHOLE archive.
    t0 = time.perf_counter()
    for _ in range(QUERIES):
        h = rng.randrange(0, height + 1)
        assert arch.header_bytes_at(h) is not None
    query_s = time.perf_counter() - t0
    # Balance + cold proofs (plane txid lookups + one record read).
    assert arch.balance(_MINER) > 0
    tx, seq_off = _tx_template(_MINER)
    from hashlib import sha256

    proofs = 0
    t0 = time.perf_counter()
    for _ in range(min(100, QUERIES)):
        h = rng.randrange(1, height + 1)
        struct.Struct(">Q").pack_into(tx, seq_off, h)
        txid = sha256(sha256(bytes(tx)).digest()).digest()
        proof = arch.tx_proof(txid)
        assert proof is not None and proof.height == h
        proofs += 1
    proof_s = time.perf_counter() - t0
    vmhwm_kb = 0
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmHWM:"):
                vmhwm_kb = int(line.split()[1])
    arch.close()
    print(
        json.dumps(
            {
                "archive_boot_s": round(boot_s, 4),
                "archive_boot_rss_mb": round(vmhwm_kb / 1024.0, 1),
                "archive_query_qps": round(QUERIES / query_s),
                "archive_proof_qps": round(proofs / proof_s),
                "height": height,
            }
        )
    )


def measure_boot(store_path: Path) -> dict:
    """Run the boot phase in a FRESH process so VmHWM is the archive
    serving footprint, not this build process's."""
    proc = subprocess.run(
        [sys.executable, __file__, "--phase", "boot", "--store", str(store_path)],
        capture_output=True,
        text=True,
        timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"boot phase failed: {proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_archive(n_blocks: int, workdir: Path) -> dict:
    """Build an ``n_blocks`` archive under ``workdir``, then measure its
    resume scan and its fresh-process boot."""
    workdir.mkdir(parents=True, exist_ok=True)
    store_path = workdir / "archive.dat"
    out = {"blocks": n_blocks}
    out.update(build_archive(store_path, n_blocks))
    out.update(measure_resume(store_path))
    out.update(measure_boot(store_path))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description="boot a built archive")
    ap.add_argument("--phase", choices=("boot",), required=True)
    ap.add_argument("--store", required=True)
    boot_phase(ap.parse_args().store)
