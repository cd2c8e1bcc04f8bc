"""Driver ``txs`` on the CPU: the backlog it signs, what the wallet is owed,
the readers of its spans, and a traced rehearsal."""

import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

from perfbench import cell, ledger_ref
from perfbench.drivers import txs

ROOT = pathlib.Path(__file__).resolve().parents[2]
TRAFFIC = json.loads((ROOT / "perfbench/traffic/d29-backlog.json").read_text())


@pytest.mark.parametrize("n,senders,cap", [(100_000, 20_000, 25), (3000, 600, 25), (50, 50, 3)])
def test_sender_counts_are_zipf_capped_and_exact(n, senders, cap):
    counts = txs.sender_counts(n, senders, 1.1, cap)
    assert sum(counts) == n and len(counts) == senders
    assert all(1 <= c <= cap for c in counts)
    assert counts[0] == min(cap, n - senders + 1)
    assert all(a >= b for a, b in zip(counts, counts[1:]))


def test_sender_counts_refuse_what_cannot_fit():
    with pytest.raises(ValueError):
        txs.sender_counts(10, 20, 1.1, 25)
    with pytest.raises(ValueError):
        txs.sender_counts(1000, 20, 1.1, 25)


def test_backlog_follows_from_the_traffic_alone():
    small = dict(TRAFFIC, difficulty=8, backlog_txs=400, senders=60)
    data = txs.build_backlog(small)
    assert data == txs.build_backlog(small)
    backlog = ledger_ref.parse_pool_file(data)
    assert len(backlog) == 400 and len({tx.txid for tx in backlog}) == 400
    counts = txs.sender_counts(400, 60, small["zipf"], small["sender_cap"])
    ranks = {a: r for r, a in enumerate(txs.hottest(small, 60))}
    next_seq: dict = {}
    for tx in backlog:  # each sender's seqs arrive in order, from 0
        assert tx.seq == next_seq.get(tx.sender, 0)
        next_seq[tx.sender] = tx.seq + 1
        assert tx.amount == tx.fee == 0 and tx.recipient in ranks
    assert {ranks[a]: n for a, n in next_seq.items()} == dict(enumerate(counts))


def test_push_txids_are_what_the_push_plane_sends():
    from p1_tpu.core import Block, BlockHeader
    from p1_tpu.core.keys import Keypair
    from p1_tpu.core.tx import Transaction
    from p1_tpu.node.subscriptions import SubscriptionManager, block_items_index

    a, b = (Keypair.from_seed_text(f"push/{i}") for i in range(2))
    block = Block(BlockHeader(1, bytes(32), bytes(32), 1, 8, 0), (
        Transaction.coinbase("m", 1), Transaction.transfer(a, b.account, 0, 0, 0),
        Transaction.transfer(b, a.account, 0, 0, 0), Transaction.transfer(a, a.account, 0, 0, 1),
        Transaction.transfer(b, "elsewhere", 0, 0, 1)))
    parts = types.SimpleNamespace(index=block_items_index(block))
    plain = [ledger_ref.parse_tx(tx.serialize())[0] for tx in block.txs]
    for watched in (["m", b.account, a.account], [a.account], ["elsewhere", "m"], ["nobody"]):
        sent = SubscriptionManager._match(None, parts, [w.encode() for w in watched])[1] \
            if any(w.encode() in parts.index for w in watched) else ()
        assert txs.push_txids(plain, watched) == sent, watched


def test_span_readers_leave_out_what_the_program_does_not_write():
    c = cell.load("solo-d29-txs")
    names = {"select_ms_per_block.txs", "evict_ms_per_block.txs", "post_seal_ms_per_block.txs"}
    entries = [m for m in c.per_layer if m["name"] in names]
    assert len(entries) == 3
    untraced = types.SimpleNamespace(spans_ms_per_block=None)
    assert cell.read_metrics(c, entries, untraced) == {}
    # A program with the mining spans and no pool spans (the one before them).
    older = types.SimpleNamespace(spans_ms_per_block={"p1.mine.post_seal": 12.5})
    assert cell.read_metrics(c, entries, older) == {
        "post_seal_ms_per_block.txs": {"value": 12.5, "unit": "ms"}}


def test_the_cell_reports_block_latency_and_its_layers_move_it():
    c = cell.load("solo-d29-txs")
    assert {m["name"] for m in c.end_to_end} == {"block_ms_p50", "setup_s"}
    assert {m["moves"] for m in c.per_layer} == {"block_ms_p50"}
    run = types.SimpleNamespace(gaps_ms=[700.0, 500.0, 900.0, 650.0], setup_s=33.0)
    assert cell.read_metrics(c, c.end_to_end, run) == {
        "block_ms_p50": {"value": 675.0, "unit": "ms"}, "setup_s": {"value": 33.0, "unit": "s"}}


def test_traced_rehearsal_reads_the_pool_spans():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-m", "perfbench.run", "--workload", "solo-d29-txs",
                           "--seed", "4200000011", "--seconds", "2", "--trace", "1",
                           "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    per_block = out["facts"]["spans"]["spans_ms_per_block"]
    for name in ("p1.mempool.select", "p1.mempool.evict", "p1.mine.post_seal"):
        assert per_block[name] > 0, name
    program = out["facts"]["program"]
    assert program["gauges"]["mine.block_txs"] == TRAFFIC["rehearsal"]["block_txs"] - 1
    assert program["counters"]["mempool.restored"] == out["facts"]["restored"]


def test_signature_check_tells_a_forged_signature():
    from p1_tpu.core.keys import Keypair
    from p1_tpu.core.tx import Transaction

    tx = ledger_ref.parse_tx(Transaction.transfer(Keypair.from_seed_text("sig"), "to", 0, 0, 0,
                                                  chain=bytes(32)).serialize())[0]
    assert txs.verify_signature(tx.pubkey, tx.sig, tx.signed)
    forged = bytes([tx.sig[0] ^ 1]) + tx.sig[1:]
    assert not txs.verify_signature(tx.pubkey, forged, tx.signed)
    assert not txs.verify_signature(tx.pubkey, tx.sig, tx.signed + b"x")
