"""Validation fast lane: batched Ed25519, verify-once cache, equivalence.

The round-8 contract under test: the batch/cache paths may change WHERE
signature-verification cost is paid, never WHAT is accepted — identical
accept/reject decisions and identical exception text against the serial
path for EVERY input, including crafted small-order torsion components:
the fallback batch subgroup-gates every point (batch acceptance implies
serial acceptance), and a failed batch is settled by serial
confirmation, so torsion crafts can slow validation down but never
change its verdict (the chain-split review fix).
"""

import dataclasses
import random

import pytest

from txutil import account, key_for, stx

from p1_tpu.chain import AddStatus, Chain, ChainStore, ValidationError, check_block
from p1_tpu.chain import validate as validate_mod
from p1_tpu.chain.store import save_chain
from p1_tpu.chain.validate import preverify_signatures
from p1_tpu.core import Block, BlockHeader, Transaction, merkle_root
from p1_tpu.core import _ed25519, keys, sigcache
from p1_tpu.core.genesis import genesis_hash
from p1_tpu.core.sigcache import SignatureCache
from p1_tpu.hashx import get_backend
from p1_tpu.miner import Miner

DIFF = 8
_MINER = Miner(backend=get_backend("cpu"))
TAG = genesis_hash(DIFF)


def _triples(n, salt="t"):
    out = []
    for i in range(n):
        kp = key_for(f"sigbatch-{salt}-{i % 5}")
        msg = b"sigbatch-%d-%s" % (i, salt.encode())
        out.append((kp.pubkey, kp.sign(msg), msg))
    return out


def _corrupt(triple, how):
    pubkey, sig, msg = triple
    if how == "sig":
        return (pubkey, sig[:20] + bytes([sig[20] ^ 1]) + sig[21:], msg)
    if how == "msg":
        return (pubkey, sig, msg + b"!")
    if how == "key":
        return (key_for("sigbatch-other").pubkey, sig, msg)
    if how == "s_range":  # scalar ≥ group order: serial rejects pre-math
        return (pubkey, sig[:32] + _ed25519._Q.to_bytes(32, "little"), msg)
    raise AssertionError(how)


# -- small-order torsion crafts (the round-8 review fix's fixtures) ------

_T2_ENC = (_ed25519._P - 1).to_bytes(32, "little")  # (0, -1): order 2
_T4_ENC = (0).to_bytes(32, "little")  # (sqrt(-1), 0): order 4


def _torsion_sign(msg: bytes, *, cancel: bool):
    """``(pubkey, sig_or_None)`` carrying small-order torsion over ``msg``.

    cancel=True: order-2 torsion planted in BOTH A and R; with k odd the
    torsion terms cancel in the serial equation, so SERIAL verification
    ACCEPTS (sig is None when k comes out even — vary the message and
    retry).  cancel=False: honest A, order-4 torsion in R — serial
    always rejects, while the pre-fix cofactored batch accepted: the
    chain-split craft.
    """
    a, prefix = _ed25519._secret_expand(bytes(32))
    B = _ed25519._B
    T = _ed25519._pt_decompress(_T2_ENC if cancel else _T4_ENC)
    a_pt = _ed25519._pt_mul(a, B)
    pub = _ed25519._pt_compress(_ed25519._pt_add(a_pt, T) if cancel else a_pt)
    r = int.from_bytes(_ed25519._sha512(prefix + msg), "little") % _ed25519._Q
    r_enc = _ed25519._pt_compress(_ed25519._pt_add(_ed25519._pt_mul(r, B), T))
    k = int.from_bytes(_ed25519._sha512(r_enc + pub + msg), "little") % _ed25519._Q
    if cancel and k % 2 == 0:
        return pub, None
    sig = r_enc + ((r + k * a) % _ed25519._Q).to_bytes(32, "little")
    return pub, sig


def _torsion_triple(*, cancel: bool, salt: bytes = b""):
    for i in range(200):
        msg = b"torsion-%d-" % i + salt
        pub, sig = _torsion_sign(msg, cancel=cancel)
        if sig is not None:
            return pub, sig, msg
    raise AssertionError("no usable k in 200 tries")


def _torsion_tx(tag: bytes, *, cancel: bool):
    """A transfer whose ownership proof is a torsion craft (see
    ``_torsion_sign``), structurally sound for ``check_block``."""
    from p1_tpu.core import keys as _k

    pub, _ = _torsion_sign(b"probe", cancel=cancel)
    sender = _k.account_id(pub)
    for seq in range(200):
        tx = Transaction(
            sender=sender,
            recipient=account("bob"),
            amount=1,
            fee=1,
            seq=seq,
            pubkey=pub,
            sig=b"",
            chain=tag,
        )
        pub2, sig = _torsion_sign(tx.signing_bytes(), cancel=cancel)
        assert pub2 == pub
        if sig is not None:
            return dataclasses.replace(tx, sig=sig)
    raise AssertionError("no usable k in 200 sequence numbers")


class TestEd25519Batch:
    """The fallback's multi-scalar batch equation against serial truth."""

    def test_rfc8032_vector_survives_decompress_rewrite(self):
        # Guards the one-exponentiation _recover_x: RFC 8032 TEST 1.
        seed = bytes.fromhex(
            "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60"
        )
        pub = bytes.fromhex(
            "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a"
        )
        assert _ed25519.public_key(seed) == pub
        sig = _ed25519.sign(seed, b"")
        assert sig.hex() == (
            "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e0652249"
            "01555fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe2465514143"
            "8e7a100b"
        )
        assert _ed25519.verify(pub, sig, b"")
        assert not _ed25519.verify(pub, sig, b"x")

    @pytest.mark.parametrize("n", [0, 1, 2, 7, 8, 9, 33])
    def test_all_valid_accepts(self, n):
        assert _ed25519.verify_batch(_triples(n))

    def test_corruption_at_every_position_rejects(self):
        base = _triples(12, salt="pos")
        for pos in range(len(base)):
            for how in ("sig", "msg", "key", "s_range"):
                bad = list(base)
                bad[pos] = _corrupt(bad[pos], how)
                assert not _ed25519.verify_batch(bad), (pos, how)
                assert not _ed25519.verify(*bad[pos])

    def test_random_mixes_match_serial(self):
        rng = random.Random(8)
        base = _triples(20, salt="mix")
        for _ in range(10):
            batch = [
                _corrupt(t, rng.choice(("sig", "msg")))
                if rng.random() < 0.2
                else t
                for t in base
            ]
            serial = all(_ed25519.verify(*t) for t in batch)
            assert _ed25519.verify_batch(batch) == serial

    def test_malformed_points_reject(self):
        kp = key_for("sigbatch-malformed")
        msg = b"m"
        sig = kp.sign(msg)
        # Non-decodable y ≥ p in the pubkey / in R.
        bad_enc = (_ed25519._P).to_bytes(32, "little")
        assert not _ed25519.verify_batch([(bad_enc, sig, msg)])
        assert not _ed25519.verify_batch([(kp.pubkey, bad_enc + sig[32:], msg)])
        assert not _ed25519.verify_batch([(kp.pubkey[:31], sig, msg)])
        assert not _ed25519.verify_batch([(kp.pubkey, sig[:63], msg)])

    def test_first_invalid_matches_serial_order(self):
        base = _triples(30, salt="first")
        for positions in ([4], [3, 17], [0, 1, 29], [29]):
            bad = list(base)
            for p in positions:
                bad[p] = _corrupt(bad[p], "sig")
            assert keys.first_invalid(bad) == min(positions)
        assert keys.first_invalid(base) is None

    def test_subgroup_gate_is_exact(self):
        # The gate must agree with the definition ([q]P == identity) on
        # torsion points, torsioned composites, and honest points.
        B = _ed25519._B
        T4 = _ed25519._pt_decompress((0).to_bytes(32, "little"))  # order 4
        T2 = _ed25519._pt_decompress(
            (_ed25519._P - 1).to_bytes(32, "little")
        )  # order 2
        assert _ed25519._in_prime_subgroup(B)
        assert _ed25519._in_prime_subgroup(_ed25519._IDENT)
        assert not _ed25519._in_prime_subgroup(T4)
        assert not _ed25519._in_prime_subgroup(T2)
        rng = random.Random(25519)
        for _ in range(8):
            honest = _ed25519._pt_mul(rng.randrange(1, _ed25519._Q), B)
            assert _ed25519._in_prime_subgroup(honest)
            for t in (T2, T4):
                mixed = _ed25519._pt_add(honest, t)
                assert not _ed25519._in_prime_subgroup(mixed)
                assert _ed25519._in_prime_subgroup(mixed) == _ed25519._pt_equal(
                    _ed25519._pt_mul(_ed25519._Q, mixed), _ed25519._IDENT
                )

    def test_torsion_craft_cannot_split_batch_from_serial(self):
        # The review fix: batch acceptance implies serial acceptance.
        # A torsion craft that serial rejects must NEVER pass the batch.
        pub, sig, msg = _torsion_triple(cancel=False)
        assert not _ed25519.verify(pub, sig, msg)
        assert not _ed25519.verify_batch([(pub, sig, msg)] * 8)
        # ...and one that serial ACCEPTS is gate-rejected by the batch,
        # then settled (accepted) by the serial confirmation.
        pub2, sig2, msg2 = _torsion_triple(cancel=True)
        assert _ed25519.verify(pub2, sig2, msg2)
        assert not _ed25519.verify_batch([(pub2, sig2, msg2)] * 8)
        assert keys.first_invalid([(pub2, sig2, msg2)] * 8) is None

    def test_first_invalid_not_steered_by_torsion_reject(self):
        # Regression for the old bisection: a torsion gate-reject in the
        # left half used to steer the search away from a genuinely bad
        # signature in the right half, returning None for a batch the
        # serial path rejects.
        base = _triples(24, salt="steer")
        tors = _torsion_triple(cancel=True)  # serially VALID, gate-rejected
        mixed = list(base)
        mixed[2] = tors
        bad_pos = 20
        mixed[bad_pos] = _corrupt(mixed[bad_pos], "sig")
        assert not _ed25519.verify_batch(mixed)
        assert keys.first_invalid(mixed) == bad_pos
        # With no genuinely bad signature, None is the (correct) verdict
        # even though the batch as a whole fails.
        mixed[bad_pos] = base[bad_pos]
        assert not _ed25519.verify_batch(mixed)
        assert keys.first_invalid(mixed) is None


class TestVerifyBatchDispatch:
    """keys.verify_batch: thresholds, worker pool, accounting."""

    def test_small_batches_run_serial(self):
        tr = _triples(keys.BATCH_MIN - 1, salt="small")
        keys.STATS.reset()
        assert keys.verify_batch(tr)
        assert keys.STATS.serial == len(tr)

    def test_large_batches_count_batched(self):
        tr = _triples(keys.BATCH_MIN, salt="large")
        keys.STATS.reset()
        assert keys.verify_batch(tr)
        assert keys.STATS.batched == len(tr)
        assert keys.STATS.serial == 0

    def test_fallback_never_dispatches_pool(self):
        # The pure-Python backend holds the GIL for its whole MSM, so
        # fanning its chunks out to worker threads is pure overhead —
        # fallback batches must run in the calling thread even when
        # workers > 1 and the batch spans multiple chunks.  The rung is
        # FORCED (round 15): on a toolchain-equipped host the auto
        # ladder resolves native, whose chunks rightly DO pool.
        old = keys._workers
        try:
            keys.set_sig_backend("fallback")
            keys.set_verify_workers(2)
            tr = _triples(16, salt="nopool") * ((keys.BATCH_CHUNK // 16) + 1)
            keys.STATS.reset()
            assert keys.verify_batch(tr)
            assert keys.STATS.pool_dispatches == 0
            assert keys.STATS.backends["pure-python"] == len(tr)
            assert keys._executor is None  # never even built
        finally:
            keys.set_sig_backend(None)
            keys.set_verify_workers(old)
            keys.shutdown_verify_pool()

    def test_pool_path_and_shutdown_cycle(self, monkeypatch):
        # Exercises the dispatch/shutdown/rebuild machinery on every
        # backend: the wheel path hits it naturally; without the wheel,
        # _use_pool is forced so the executor lifecycle still runs.
        if not keys.HAVE_CRYPTOGRAPHY:
            monkeypatch.setattr(keys, "_use_pool", lambda n_chunks: n_chunks > 1)
        old = keys._workers
        try:
            keys.set_verify_workers(2)
            tr = _triples(16, salt="pool") * ((keys.BATCH_CHUNK // 16) + 1)
            keys.STATS.reset()
            assert keys.verify_batch(tr)  # > one chunk => pool dispatch
            assert keys.STATS.pool_dispatches == 1
            keys.shutdown_verify_pool()
            assert keys.verify_batch(tr[: keys.BATCH_MIN])  # pool rebuilt ok
            bad = list(tr)
            bad[len(bad) // 2] = _corrupt(bad[len(bad) // 2], "sig")
            assert not keys.verify_batch(bad)
        finally:
            keys.set_verify_workers(old)
            keys.shutdown_verify_pool()

    def test_fallback_warning_fires_once(self, caplog):
        # Forced onto the pure-Python rung (the auto ladder resolves a
        # faster backend wherever one exists): the one-time cost-model
        # warning must fire exactly once, say that this is the slowest
        # rung, and name the fastest backend story for THIS host.
        keys._fallback_warned = False
        try:
            keys.set_sig_backend("fallback")
            with caplog.at_level("WARNING", logger="p1_tpu.core.keys"):
                keys.verify_batch(_triples(keys.BATCH_MIN, salt="warn"))
                keys.verify_batch(_triples(keys.BATCH_MIN, salt="warn2"))
        finally:
            keys.set_sig_backend(None)
            keys._fallback_warned = False
        hits = [r for r in caplog.records if "pure-Python Ed25519" in r.message]
        assert len(hits) == 1
        msg = hits[0].getMessage()
        assert "slowest rung" in msg  # names the slowdown
        assert "FORCED" in msg  # ...and that this rung was an explicit pin

    @pytest.mark.slow
    def test_pool_cancellation_mid_batch(self, monkeypatch):
        # The soak the conftest knob (workers=1 default) excludes from
        # tier-1: a pool torn down with futures in flight must not
        # change the batch's answer — cancelled chunks re-verify in the
        # calling thread.
        import threading

        if not keys.HAVE_CRYPTOGRAPHY:
            monkeypatch.setattr(keys, "_use_pool", lambda n_chunks: n_chunks > 1)
        old = keys._workers
        try:
            keys.set_verify_workers(3)
            tr = _triples(64, salt="cancel") * ((2 * keys.BATCH_CHUNK) // 64)
            for _ in range(5):
                keys._pool(3)  # ensure a pool exists to tear down
                t = threading.Timer(
                    0.001, keys.shutdown_verify_pool, kwargs={"cancel": True}
                )
                t.start()
                assert keys.verify_batch(tr)
                t.join()
        finally:
            keys.set_verify_workers(old)
            keys.shutdown_verify_pool()


def _mine(parent, txs, ts=1):
    header = BlockHeader(
        version=1,
        prev_hash=parent.block_hash(),
        merkle_root=merkle_root([t.txid() for t in txs]),
        timestamp=parent.header.timestamp + ts,
        difficulty=DIFF,
        nonce=0,
    )
    sealed = _MINER.search_nonce(header)
    assert sealed is not None
    return Block(sealed, tuple(txs))


def _funded_chain():
    """A chain whose 'alice' can afford many transfers (mined rewards)."""
    chain = Chain(DIFF)
    for h in range(1, 4):
        blk = _mine(chain.tip, [Transaction.coinbase(account("alice"), h)])
        assert chain.add_block(blk).status is AddStatus.ACCEPTED
    return chain


def _transfers(n, start_seq=0):
    return [
        stx("alice", account("bob"), 1, 1, start_seq + i, difficulty=DIFF)
        for i in range(n)
    ]


@pytest.fixture
def serial_lane(monkeypatch):
    """Force the pre-round-8 cost model: per-tx backend verifies, no
    batching, no pre-warm — the equivalence baseline."""
    monkeypatch.setattr(keys, "BATCH_MIN", 1 << 30)
    monkeypatch.setattr(
        validate_mod, "preverify_signatures", lambda *a, **k: 0
    )
    import p1_tpu.chain.store as store_mod

    monkeypatch.setattr(
        store_mod, "_preverify_stream", lambda blocks, tag, cache: blocks
    )


class TestCheckBlockEquivalence:
    """check_block batch path == serial path, error text included."""

    def _block_with(self, txs):
        chain = _funded_chain()
        return chain, _mine(chain.tip, txs)

    def _outcome(self, chain, block, cache):
        try:
            check_block(
                block,
                DIFF,
                chain_tag=chain.genesis.block_hash(),
                sig_cache=cache,
            )
            return None
        except ValidationError as e:
            return str(e)

    def test_valid_block_all_paths(self, monkeypatch):
        chain, block = self._block_with(
            [Transaction.coinbase(account("m"), 4), *_transfers(10)]
        )
        assert self._outcome(chain, block, SignatureCache()) is None  # batch
        monkeypatch.setattr(keys, "BATCH_MIN", 1 << 30)
        assert self._outcome(chain, block, SignatureCache()) is None  # serial
        warm = SignatureCache()
        preverify_signatures(block.txs, chain.genesis.block_hash(), warm)
        keys.STATS.reset()
        assert self._outcome(chain, block, warm) is None  # cache-hit
        assert keys.STATS.serial == 0 and keys.STATS.batched == 0

    def test_corrupted_sig_every_position_identical_error(self, monkeypatch):
        txs = _transfers(10)
        for pos in range(len(txs)):
            bad_txs = list(txs)
            bad_txs[pos] = dataclasses.replace(
                bad_txs[pos],
                sig=_corrupt(
                    (b"", bad_txs[pos].sig, b""), "sig"
                )[1],
            )
            chain, block = self._block_with(
                [Transaction.coinbase(account("m"), 4), *bad_txs]
            )
            batch_err = self._outcome(chain, block, SignatureCache())
            with monkeypatch.context() as m:
                m.setattr(keys, "BATCH_MIN", 1 << 30)
                serial_err = self._outcome(chain, block, SignatureCache())
            assert batch_err == serial_err == "bad transaction signature", pos

    def test_structural_vs_signature_precedence(self, monkeypatch):
        # Serial interleaving: an EARLIER bad signature outranks a later
        # structural failure; a structural failure before any bad
        # signature is what gets reported.  Both paths must agree.
        good = _transfers(9)
        foreign = dataclasses.replace(
            stx("alice", account("bob"), 1, 1, 50, difficulty=DIFF),
            chain=genesis_hash(DIFF + 1),
        )
        bad_sig = dataclasses.replace(
            good[2], sig=_corrupt((b"", good[2].sig, b""), "sig")[1]
        )
        cases = [
            # (txs, expected error): foreign tag after a bad signature
            ([*good[:2], bad_sig, *good[3:], foreign], "bad transaction signature"),
            # foreign tag with every signature before it valid
            ([*good[:5], foreign, *good[5:]], "transaction signed for a different chain"),
            # signed coinbase reported over a later bad signature? no —
            # the coinbase slot fails structurally FIRST serially too.
            ([dataclasses.replace(Transaction.coinbase(account("m"), 4), sig=b"x" * 64), *good[:3]], "coinbase must be unsigned"),
        ]
        for txs, expected in cases:
            chain, block = self._block_with(txs)
            batch_err = self._outcome(chain, block, SignatureCache())
            with monkeypatch.context() as m:
                m.setattr(keys, "BATCH_MIN", 1 << 30)
                serial_err = self._outcome(chain, block, SignatureCache())
            assert batch_err == serial_err == expected, txs

    def test_torsion_tx_outcomes_identical(self, monkeypatch):
        # End to end: a block carrying a torsion-crafted ownership proof
        # must land the SAME way on the batch lane (gate reject → serial
        # confirmation) as on the pure serial lane — on every node,
        # whichever backend it has.  cancel=True is serially VALID (the
        # block is accepted despite the failed batch); cancel=False is
        # the old chain-split craft (rejected everywhere, same text).
        cases = [
            (_torsion_tx(TAG, cancel=True), None),
            (_torsion_tx(TAG, cancel=False), "bad transaction signature"),
        ]
        for crafted, expected in cases:
            txs = [*_transfers(keys.BATCH_MIN), crafted]  # batch lane engages
            chain, block = self._block_with(txs)
            batch_err = self._outcome(chain, block, SignatureCache())
            with monkeypatch.context() as m:
                m.setattr(keys, "BATCH_MIN", 1 << 30)
                serial_err = self._outcome(chain, block, SignatureCache())
            assert batch_err == serial_err == expected

    def test_fingerprint_mismatch_identical(self, monkeypatch):
        victim = _transfers(9)
        forged = dataclasses.replace(
            victim[4], pubkey=key_for("sigbatch-thief").pubkey
        )
        txs = [*victim[:4], forged, *victim[5:]]
        chain, block = self._block_with(txs)
        batch_err = self._outcome(chain, block, SignatureCache())
        with monkeypatch.context() as m:
            m.setattr(keys, "BATCH_MIN", 1 << 30)
            serial_err = self._outcome(chain, block, SignatureCache())
        assert batch_err == serial_err == "bad transaction signature"


#: Every signature backend THIS host can run for the equivalence
#: matrix: the pure-Python rung always, the native C++ engine when a
#: toolchain (or cached build) exists, the wheel when installed.  The
#: device rung's matrix lives in tests/test_ed25519_device.py (slow —
#: its jit compile dwarfs the tier-1 budget).
_MATRIX_BACKENDS = ["fallback"]
if keys._native_ed25519.available():
    _MATRIX_BACKENDS.append("native")
if keys.HAVE_CRYPTOGRAPHY:
    _MATRIX_BACKENDS.append("cryptography")


@pytest.fixture(params=_MATRIX_BACKENDS)
def each_backend(request):
    """Pin one backend rung for the duration of a test."""
    keys.set_sig_backend(request.param)
    yield request.param
    keys.set_sig_backend(None)


class TestBackendEquivalenceMatrix:
    """Round-15 satellite: the SAME verdict and the SAME error text on
    every backend rung, for every input — honest, corrupted at every
    position, and torsion-crafted.  The serial lane (BATCH_MIN forced
    high) on the pure-Python rung is the consensus baseline."""

    def _outcome(self, txs, backend):
        chain = _funded_chain()
        block = _mine(chain.tip, txs)
        try:
            check_block(
                block,
                DIFF,
                chain_tag=chain.genesis.block_hash(),
                sig_cache=SignatureCache(),
            )
            return None
        except ValidationError as e:
            return str(e)

    def test_valid_block_accepts_everywhere(self, each_backend):
        txs = [Transaction.coinbase(account("m"), 4), *_transfers(10)]
        assert self._outcome(txs, each_backend) is None

    def test_corruption_at_every_position_same_error(
        self, each_backend, monkeypatch
    ):
        txs = _transfers(10)
        for pos in range(len(txs)):
            bad_txs = list(txs)
            bad_txs[pos] = dataclasses.replace(
                bad_txs[pos],
                sig=_corrupt((b"", bad_txs[pos].sig, b""), "sig")[1],
            )
            block_txs = [Transaction.coinbase(account("m"), 4), *bad_txs]
            got = self._outcome(block_txs, each_backend)
            with monkeypatch.context() as m:
                # serial pure-Python: the consensus baseline
                m.setattr(keys, "BATCH_MIN", 1 << 30)
                keys.set_sig_backend("fallback")
                try:
                    want = self._outcome(block_txs, "serial")
                finally:
                    keys.set_sig_backend(each_backend)
            assert got == want == "bad transaction signature", (
                each_backend,
                pos,
            )

    def test_torsion_fixtures_same_verdict_and_text(
        self, each_backend, monkeypatch
    ):
        cases = [
            (_torsion_tx(TAG, cancel=True), None),
            (_torsion_tx(TAG, cancel=False), "bad transaction signature"),
        ]
        for crafted, expected in cases:
            txs = [*_transfers(keys.BATCH_MIN), crafted]
            got = self._outcome(txs, each_backend)
            assert got == expected, (each_backend, expected)

    def test_first_invalid_left_first_on_every_backend(self, each_backend):
        base = _triples(24, salt="matrix-" + each_backend)
        tors = _torsion_triple(cancel=True)  # serially valid, gate-rejected
        mixed = list(base)
        mixed[2] = tors
        mixed[20] = _corrupt(mixed[20], "sig")
        assert not keys.verify_batch(mixed)
        assert keys.first_invalid(mixed) == 20
        mixed[20] = base[20]
        assert keys.first_invalid(mixed) is None


class TestPreverify:
    def test_warms_only_valid_sigs(self):
        txs = _transfers(12)
        bad = dataclasses.replace(
            txs[5], sig=_corrupt((b"", txs[5].sig, b""), "sig")[1]
        )
        foreign = dataclasses.replace(txs[7], chain=b"\x00" * 32)
        mixed = [*txs[:5], bad, txs[6], foreign, *txs[8:], Transaction.coinbase("m", 1)]
        cache = SignatureCache()
        proven = preverify_signatures(mixed, TAG, cache)
        assert proven == 10  # 12 transfers minus the corrupted + foreign
        assert cache.hit(txs[0].txid(), txs[0].pubkey, txs[0].sig)
        assert not cache.hit(bad.txid(), bad.pubkey, bad.sig)
        assert not cache.hit(foreign.txid(), foreign.pubkey, foreign.sig)

    def test_warm_then_cold_outcomes_identical(self):
        # The warmer is an accelerator, not an oracle: a block whose
        # signatures were pre-proven and one validated cold must agree.
        chain_w, chain_c = _funded_chain(), _funded_chain()
        block = _mine(
            chain_w.tip, [Transaction.coinbase(account("m"), 4), *_transfers(10)]
        )
        preverify_signatures(block.txs, chain_w.genesis.block_hash(), chain_w.sig_cache)
        assert chain_w.add_block(block).status is AddStatus.ACCEPTED
        assert chain_c.add_block(block).status is AddStatus.ACCEPTED
        assert chain_w.tip_hash == chain_c.tip_hash


class TestRevalidateEquivalence:
    def _build_store(self, tmp_path, n_blocks=24):
        chain = _funded_chain()
        seq = 0
        for h in range(4, 4 + n_blocks):
            txs = [Transaction.coinbase(account("alice"), h), *_transfers(3, seq)]
            seq += 3
            assert chain.add_block(_mine(chain.tip, txs)).status is AddStatus.ACCEPTED
        path = tmp_path / "reval.chain"
        save_chain(chain, path)
        return chain, path

    @staticmethod
    def _state(chain):
        return (
            chain.tip_hash,
            chain.height,
            chain.balances_snapshot(),
            {a: chain.nonce(a) for a in ("alice", "bob")},
        )

    def test_batch_equals_serial_revalidation(self, tmp_path, serial_lane, monkeypatch):
        built, path = self._build_store(tmp_path)
        serial = ChainStore(path).load_chain(DIFF, trusted=False, sig_cache=SignatureCache())
        monkeypatch.undo()  # restore the batch lane
        batch = ChainStore(path).load_chain(DIFF, trusted=False, sig_cache=SignatureCache())
        assert self._state(serial) == self._state(batch) == self._state(built)

    def test_corrupt_record_same_rejection_both_lanes(self, tmp_path, monkeypatch):
        built, path = self._build_store(tmp_path, n_blocks=12)
        # Corrupt ONE signature inside a mid-chain record, CRC-fixed so
        # the storage layer hands it through and VALIDATION must catch it
        # (store.py's "hostile editor, not a disk" case).
        raw = bytearray(path.read_bytes())
        target = built._main_hashes[8]
        body = built.get(target).serialize()
        off = raw.find(body)
        assert off > 0
        sig_field = built.get(target).txs[1].sig
        soff = raw.find(sig_field, off)
        raw[soff] ^= 1
        # fix the record checksum: recompute over the framed record
        import struct
        import zlib

        rec_off = off - 4
        (length,) = struct.unpack_from(">I", raw, rec_off)
        crc = zlib.crc32(raw[rec_off : rec_off + 4 + length])
        struct.pack_into(">I", raw, rec_off + 4 + length, crc)
        path.write_bytes(bytes(raw))

        def load():
            return ChainStore(path).load_chain(
                DIFF, trusted=False, sig_cache=SignatureCache()
            )

        batch_chain = load()
        with monkeypatch.context() as m:
            m.setattr(keys, "BATCH_MIN", 1 << 30)
            m.setattr(validate_mod, "preverify_signatures", lambda *a, **k: 0)
            import p1_tpu.chain.store as store_mod

            m.setattr(
                store_mod, "_preverify_stream", lambda blocks, tag, cache: blocks
            )
            serial_chain = load()
        # Both lanes reject the tampered record (and its descendants,
        # which no longer connect) at the same height.
        assert batch_chain.height == serial_chain.height == 8 - 1

    def test_trusted_resume_is_signature_free_and_unchanged(self, tmp_path):
        built, path = self._build_store(tmp_path, n_blocks=12)
        keys.STATS.reset()
        resumed = ChainStore(path).load_chain(DIFF, trusted=True)
        assert keys.STATS.serial == 0
        assert keys.STATS.batched == 0
        assert self._state(resumed) == self._state(built)


class TestSignatureCache:
    def test_lru_bound_and_counters(self):
        cache = SignatureCache(max_entries=4)
        items = [(bytes([i]) * 32, b"p" * 32, b"s" * 64) for i in range(6)]
        for it in items:
            cache.add(*it)
        assert len(cache) == 4
        assert cache.bytes_used == 4 * sigcache.ENTRY_COST
        assert not cache.hit(*items[0])  # evicted (oldest)
        assert cache.hit(*items[5])
        assert cache.snapshot()["hits"] == 1
        assert cache.snapshot()["misses"] == 1

    def test_lru_refresh_on_hit(self):
        cache = SignatureCache(max_entries=2)
        a, b, c = [(bytes([i]) * 32, b"p" * 32, b"s" * 64) for i in range(3)]
        cache.add(*a)
        cache.add(*b)
        assert cache.hit(*a)  # refresh a; b is now oldest
        cache.add(*c)
        assert not cache.hit(*b)
        assert cache.hit(*a)

    def test_salted_keys_differ_across_instances(self):
        a, b = SignatureCache(), SignatureCache()
        txid, pk, sg = b"\x01" * 32, b"p" * 32, b"s" * 64
        assert a._key(txid, pk, sg) != b._key(txid, pk, sg)

    def test_failures_never_cached(self):
        cache = SignatureCache()
        tx = stx("alice", account("bob"), 1, 1, 0, difficulty=DIFF)
        bad = dataclasses.replace(tx, sig=bytes(64))
        assert not bad.verify_signature(cache=cache)
        assert len(cache) == 0
        assert tx.verify_signature(cache=cache)
        assert len(cache) == 1


class TestNegativeVerifyCache:
    """keys.verify's bounded negative memo (round-8 review, finding 3):
    a peer replaying a known-bad signature must not buy a fresh backend
    verify every time."""

    def test_replayed_invalid_costs_one_backend_call(self):
        kp = key_for("sigbatch-negcache")
        msg = b"neg-memo"
        bad_sig = bytes(64)
        keys._neg_cache.clear()
        keys.STATS.reset()
        assert not keys.verify(kp.pubkey, bad_sig, msg)
        assert keys.STATS.serial == 1
        for _ in range(5):
            assert not keys.verify(kp.pubkey, bad_sig, msg)
        assert keys.STATS.serial == 1  # the memo absorbed the replays
        # Positive results are NOT memoized here (that's sigcache's job,
        # keyed by txid): each valid verify still reaches the backend.
        good = kp.sign(msg)
        assert keys.verify(kp.pubkey, good, msg)
        assert keys.verify(kp.pubkey, good, msg)
        assert keys.STATS.serial == 3

    def test_negative_memo_is_bounded(self, monkeypatch):
        monkeypatch.setattr(keys, "_NEG_CACHE_MAX", 4)
        keys._neg_cache.clear()
        kp = key_for("sigbatch-negbound")
        for i in range(8):
            assert not keys.verify(kp.pubkey, bytes(64), b"m%d" % i)
        assert len(keys._neg_cache) <= 4
        keys._neg_cache.clear()

    def test_memo_key_commits_to_exact_bytes(self):
        kp = key_for("sigbatch-negexact")
        keys._neg_cache.clear()
        assert not keys.verify(kp.pubkey, bytes(64), b"a")
        # Same pubkey, different message: its own verdict, not a shadow.
        msg = b"b"
        assert keys.verify(kp.pubkey, kp.sign(msg), msg)


class TestNoDoubleVerify:
    """The mempool-admission → block-connect double-verify fix."""

    def test_mempool_then_block_connect_zero_backend_calls(self):
        from p1_tpu.mempool import Mempool

        chain = _funded_chain()
        cache = SignatureCache()
        chain.sig_cache = cache
        pool = Mempool(
            balance_of=chain.balance,
            nonce_of=chain.nonce,
            chain_tag=chain.genesis.block_hash(),
            sig_cache=cache,
        )
        txs = _transfers(10)
        keys.STATS.reset()
        for tx in txs:
            assert pool.add(tx)
        admitted = keys.STATS.serial + keys.STATS.batched
        assert admitted == len(txs)  # admission paid the backend once each
        # Mine-time assembly + connect: all signatures cache-hit.
        block = _mine(
            chain.tip,
            [Transaction.coinbase(account("m"), 4), *pool.select(100)],
        )
        keys.STATS.reset()
        assert chain.add_block(block).status is AddStatus.ACCEPTED
        assert keys.STATS.serial == 0
        assert keys.STATS.batched == 0
        assert cache.hits >= len(txs)


class TestNodeValidationStatus:
    """Node-level acceptance: a fully mempool-resident block connects
    with ZERO backend Ed25519 verifies, and status() exposes the
    counters."""

    def test_mempool_resident_block_connects_backend_free(self):
        import asyncio

        from p1_tpu.config import NodeConfig
        from p1_tpu.node import Node

        async def scenario():
            node = Node(
                NodeConfig(difficulty=DIFF, mine=False, chunk=1 << 14)
            )
            await node.start()
            try:
                # Fund alice so her spends are admissible.
                import time as _time

                node.miner_id = account("alice")
                node.start_mining()
                deadline = _time.monotonic() + 20
                while node.chain.height < 3:
                    assert _time.monotonic() < deadline
                    await asyncio.sleep(0.02)
                await node.stop_mining()
                tag = node.chain.genesis.block_hash()
                for i in range(10):
                    await node.submit_tx(
                        stx("alice", account("bob"), 1, 1, i, difficulty=DIFF)
                    )
                assert len(node.mempool) == 10
                block = node._assemble()
                sealed = _MINER.search_nonce(block.header)
                block = Block(sealed, block.txs)
                keys.STATS.reset()
                hits_before = node.sig_cache.hits
                res = await node._handle_block(block)
                assert res.status is AddStatus.ACCEPTED
                assert keys.STATS.serial == 0  # zero backend verifies
                assert keys.STATS.batched == 0
                assert node.sig_cache.hits - hits_before >= 10
                validation = node.status()["validation"]
                assert validation["hits"] >= 10
                assert validation["entries"] >= 10
                assert {"misses", "batched", "serial", "backend", "workers"} <= set(
                    validation
                )
            finally:
                await node.stop()

        asyncio.run(asyncio.wait_for(scenario(), timeout=60))
