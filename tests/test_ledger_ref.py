"""The full node's plain reference (``perfbench/ledger_ref.py``) against
the program, and the node against the reference end to end.

The reference imports nothing of the program; here each of its parts is
held to the program's own: the selection rule to ``Mempool.select`` on
seeded pools (Zipf-skewed senders, seq gaps, fee ties, senders who cannot
pay), the ledger to ``chain.ledger.Ledger``, the merkle root and the
transfer's wire layout to ``core``'s.  Then the benchmark's ``txs``
driver runs a mining node over a restored pool on the CPU at rehearsal
size and compares every block, the ledger and the pool with the
reference; its control, which leaves each template's last transfer out,
must read not correct.
"""

from __future__ import annotations

import json
import os
import pathlib
import random
import subprocess
import sys

import pytest

from p1_tpu.chain.ledger import Ledger
from p1_tpu.core.block import Block, merkle_root
from p1_tpu.core.header import BlockHeader
from p1_tpu.core.keys import Keypair
from p1_tpu.core.tx import Transaction
from p1_tpu.mempool import Mempool
from perfbench import ledger_ref

ROOT = pathlib.Path(__file__).resolve().parents[1]
KEYS = [Keypair.from_seed_text(f"ledger-ref/{i}") for i in range(24)]
TAG = bytes(range(32))


def _plain(tx: Transaction) -> ledger_ref.Tx:
    return ledger_ref.parse_tx(tx.serialize())[0]


def _pool(seed: int):
    """A seeded pool: Zipf-skewed pending counts, some seqs skipped (a
    gap strands the sender's later seqs), fees from a small set (ties),
    amounts some senders cannot cover, confirmed nonces above 0."""
    rng = random.Random(seed)
    n = rng.randint(3, len(KEYS))
    nonce = {k.account: rng.choice([0, 0, 1, 3]) for k in KEYS[:n]}
    balance = {k.account: rng.choice([0, 5, 40, 1000]) for k in KEYS[:n]}
    pool = Mempool(max_txs=10_000, balance_of=lambda a: balance.get(a, 0),
                   nonce_of=lambda a: nonce.get(a, 0))
    for rank, key in enumerate(KEYS[:n], 1):
        pending = min(25, max(1, int(12 * rank ** -1.1 * rng.uniform(0.5, 2))))
        seq = nonce[key.account] + (rng.random() < 0.15)  # a gap at the start
        for _ in range(pending):
            if rng.random() < 0.1:
                seq += 1  # a gap inside the run
            tx = Transaction.transfer(key, rng.choice(KEYS).account, rng.choice([0, 1, 2]),
                                      rng.choice([0, 1, 1, 2, 5]), seq, chain=TAG)
            pool.add(tx)
            seq += 1
    return pool, nonce, balance


@pytest.mark.parametrize("seed", range(12))
def test_selection_rule_equals_mempool_select(seed):
    pool, nonce, balance = _pool(seed)
    pending = ledger_ref.pending_by_sender(_plain(tx) for tx in pool._txs.values())
    assert len(pool) > 0
    for max_txs in (1, 7, 40, 10_000):
        want = [tx.txid() for tx in pool.select(max_txs)]
        got = [tx.txid for tx in ledger_ref.select(pending, lambda a: nonce.get(a, 0),
                                                    lambda a: balance.get(a, 0), max_txs)]
        assert got == want, max_txs


@pytest.mark.parametrize("n", [0, 1, 2, 3, 7, 1000])
def test_merkle_root_equals_the_program_s(n):
    rng = random.Random(n)
    txids = [rng.randbytes(32) for _ in range(n)]
    assert ledger_ref.merkle_root(txids) == merkle_root(txids)


def test_wire_layout_and_pool_file_match_the_program_s():
    from p1_tpu.mempool import dump_mempool

    txs = [Transaction.transfer(KEYS[i], KEYS[i + 1].account, i, 2 * i, 3 * i, chain=TAG)
           for i in range(5)] + [Transaction.coinbase("miner", 7)]
    for tx in txs:
        plain = _plain(tx)
        assert (plain.sender, plain.recipient, plain.amount, plain.fee, plain.seq) == \
            (tx.sender, tx.recipient, tx.amount, tx.fee, tx.seq)
        assert (plain.chain, plain.pubkey, plain.sig) == (tx.chain, tx.pubkey, tx.sig)
        assert plain.txid == tx.txid() and plain.signed == tx.signing_bytes()
        assert plain.is_coinbase == tx.is_coinbase
    for tx in txs[:-1]:
        assert ledger_ref.account_of(tx.pubkey) == tx.sender
    parsed = ledger_ref.parse_pool_file(dump_mempool([(tx, 1.5) for tx in txs]))
    assert [p.txid for p in parsed] == [tx.txid() for tx in txs]


def test_ledger_equals_the_program_s_over_valid_blocks():
    rng = random.Random(7)
    program, ref = Ledger(), ledger_ref.Ledger()
    miner = KEYS[0]
    for height in range(1, 30):
        txs = [Transaction.coinbase(miner.account, height)]
        for _ in range(rng.randint(0, 6)):
            key = rng.choice(KEYS[:6])
            have = program.balance(key.account)
            if have == 0:
                continue
            fee = rng.randint(0, min(3, have))
            amount = rng.randint(0, have - fee)
            seq = program.nonce(key.account) + sum(1 for t in txs if t.sender == key.account)
            # Spend only what the running block leaves the sender.
            spent = sum(t.amount + t.fee for t in txs if t.sender == key.account)
            if amount + fee + spent > have:
                continue
            txs.append(Transaction.transfer(key, rng.choice(KEYS[:6]).account, amount, fee, seq))
        block = Block(BlockHeader(1, bytes(32), bytes(32), height, 8, 0), tuple(txs))
        program.apply_block(block)
        assert ref.apply([_plain(tx) for tx in txs], height) == []
        assert ref.balances == program.snapshot()
        assert ref.nonces == program.nonces_snapshot()
    # A replayed seq and an overdraw are faults of the reference too.
    spender = next(k for k in KEYS[:6] if ref.nonce(k.account) > 0)
    replay = Transaction.transfer(spender, miner.account, 0, 0, 0)
    coinbase = _plain(Transaction.coinbase(miner.account, 30))
    assert any("seq" in f for f in ref.apply([coinbase, _plain(replay)], 30))
    unfunded = Transaction.transfer(KEYS[20], miner.account, 1, 0, 0)
    coinbase = _plain(Transaction.coinbase(miner.account, 31))
    assert any("overdraws" in f for f in ref.apply([coinbase, _plain(unfunded)], 31))


def _run(module: str, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", module, "--workload", "solo-d29-txs", *args,
                           "--seconds", "2", "--rehearse"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def _results(stdout: str) -> list[dict]:
    return [json.loads(line) for line in stdout.splitlines() if line.startswith('{"correct"')]


def test_node_blocks_ledger_and_pool_match_the_reference():
    proc = _run("perfbench.run", "--seed", "4100000007")
    assert proc.returncode == 0, proc.stderr[-3000:]
    (out,) = _results(proc.stdout)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    facts = out["facts"]
    assert facts["restored"] == 3000
    assert facts["window_transfers"] == 7 * facts["blocks"]  # every window block full
    assert facts["pool_left"] > 0
    assert facts["program"]["counters"]["mempool.restored"] == 3000


def test_control_leaving_out_a_transfer_is_not_correct():
    proc = _run("perfbench.control", "--seeds", "5")
    (out,) = _results(proc.stdout)
    assert out["correct"] is False
    wrong = {name for name, c in out["checks"].items() if c["value"] > c["limit"]}
    assert wrong == {"composition_diff", "not_full"}
