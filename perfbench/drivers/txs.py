"""Driver ``txs``: a mining full node restarted onto a congested pool.

The node is the one driver ``mine`` runs (``perfbench/drive.py``
``build_node``: ``p1 node --backend tpu``, the wall clock held at genesis),
with its store's pool file, ``<store>.mempool``, written before it starts.
The node restores that file on its normal start path (``_load_mempool``:
every record passes full admission, its Ed25519 signature included), then
mines full blocks from it: each template is ``Mempool.select`` over what
is pending, and each sealed block evicts what it confirmed.  Nothing
arrives during the run (a closed loop), so every seed mines the same
chain, and every window block is drawn from a pool fixed before the
window opens.

The backlog follows from the traffic's numbers alone: ``senders``
keypairs named from ``chain``; the sender of rank ``r`` (from 1) holds
about ``C * r**-zipf`` pending transfers, at least 1 and at most
``sender_cap``, with ``C`` set so that they sum to ``backlog_txs``; each
transfer pays a recipient drawn uniformly from the senders, amount 0 and
fee 0, its seqs gap-free from 0; the records lie in a seeded arrival
order that keeps each sender's seqs ascending.  It is signed once and kept
as the node's own pool file under the checkout (``CHAINS``), built again
when absent; the seconds spent building or loading it are a fact of the
run and part of its set-up.

Beside the node, the push wallet watches the miner and the ``watched``
senders with the most pending transfers, so its events carry matched
txids.  After the window the node stops mining, and the run reads the
pool over the wire (``GETMEMPOOL``), the ledger from the node, and the
blocks from its store; ``compare`` holds them to ``perfbench/ledger_ref.py``.
A traced run reads the program's ``p1.*`` spans over the window
(``perfbench/spans.py``).  Every run also notes, among its facts, the host
gap before each window search and the pauses inside the window that the
node's own spans do not name: the interpreter's collections and the pool's
periodic upkeep (its expiry pass and checkpoint snapshot).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import gc
import os
import pathlib
import random
import tempfile
import time

from perfbench import checks, ledger_ref, reference, spans, trace
from perfbench import drive as node_drive
from perfbench import view as node_view
from perfbench.drivers import mine

SPAN = node_drive.SEARCH_SPAN
IDLE = ("host in search_nonce: dispatch and readback",
        "host outside search_nonce: node select, connect, evict, store, push")
#: Where the signed backlog is kept, under the checkout (git-ignored).
CHAINS = ".perfbench_chains"
#: The program's counters and histograms a run reports, by prefix.
SERIES = ("mempool.", "mine.", "validation.")


@dataclasses.dataclass
class Window(node_drive.Window):
    backlog: bytes = b""  # the pool file, as kept
    backlog_s: float = 0.0
    backlog_built: bool = False
    block_txs: int = 0  # the block size the node was run with
    watched: list = dataclasses.field(default_factory=list)  # the wallet's accounts
    restored: int | None = None  # the pool's size right after its restore
    blocks_stored: list = dataclasses.field(default_factory=list)  # Blocks, from genesis
    chain: object = None  # the node's chain after the run: its ledger
    pool: list | None = None  # wire bytes of what the node held at the end
    series: dict = dataclasses.field(default_factory=dict)  # the program's, at the end
    span_report: dict | None = None  # spans.report over the window, traced runs
    #: (what, start, seconds) of the interpreter's collections ("gc<generation>")
    #: and of the pool's periodic upkeep (its expiry pass and checkpoint snapshot)
    pauses: list = dataclasses.field(default_factory=list)


def sender_counts(n_txs: int, n_senders: int, zipf: float, cap: int) -> list[int]:
    """Pending transfers of the sender of each rank: ``min(cap, max(1,
    floor(C * r**-zipf)))`` at the smallest ``C`` whose sum reaches
    ``n_txs``, less one from each of the lowest ranks above 1 until the
    sum is ``n_txs``."""
    if not n_senders <= n_txs <= n_senders * cap:
        raise ValueError(f"{n_txs} transfers cannot spread over {n_senders} senders, "
                         f"1 to {cap} each")
    weights = [r ** -zipf for r in range(1, n_senders + 1)]

    def counts(c):
        return [min(cap, max(1, int(c * w))) for w in weights]

    lo, hi = 0.0, float(cap) / weights[-1]
    for _ in range(200):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if sum(counts(mid)) < n_txs else (lo, mid)
    out = counts(hi)
    excess = sum(out) - n_txs
    for i in range(n_senders - 1, -1, -1):
        if excess == 0:
            break
        if out[i] > 1:
            out[i] -= 1
            excess -= 1
    return out


def senders(traffic: dict) -> list:
    from p1_tpu.core.keys import Keypair

    return [Keypair.from_seed_text(f"{traffic['chain']}/sender/{r}")
            for r in range(1, traffic["senders"] + 1)]


def build_backlog(traffic: dict) -> bytes:
    """The signed backlog as the node's pool file, every age 0."""
    from p1_tpu.core.genesis import make_genesis
    from p1_tpu.core.tx import Transaction
    from p1_tpu.mempool import dump_mempool

    keys = senders(traffic)
    counts = sender_counts(traffic["backlog_txs"], len(keys), traffic["zipf"],
                           traffic["sender_cap"])
    rng = random.Random(f"{traffic['chain']}/backlog")
    arrivals = [r for r, n in enumerate(counts) for _ in range(n)]
    rng.shuffle(arrivals)
    tag = make_genesis(traffic["difficulty"]).block_hash()
    seq = [0] * len(keys)
    rows = []
    for r in arrivals:
        to = keys[rng.randrange(len(keys))].account
        rows.append((Transaction.transfer(keys[r], to, 0, 0, seq[r], chain=tag), 0.0))
        seq[r] += 1
    return dump_mempool(rows)


def backlog_path(root: pathlib.Path, traffic: dict) -> pathlib.Path:
    name = (f"{traffic['chain']}-d{traffic['difficulty']}-n{traffic['backlog_txs']}"
            f"-s{traffic['senders']}-z{traffic['zipf']}-c{traffic['sender_cap']}.mempool")
    return root / CHAINS / name


def load_backlog(root: pathlib.Path, traffic: dict) -> tuple[bytes, bool]:
    """The pool file and whether it was built in this run."""
    path = backlog_path(root, traffic)
    if path.is_file():
        return path.read_bytes(), False
    data = build_backlog(traffic)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    tmp.write_bytes(data)
    os.replace(tmp, path)
    return data, True


def hottest(traffic: dict, k: int) -> list[str]:
    """The accounts of the ``k`` senders with the most pending transfers."""
    return [kp.account for kp in senders(dict(traffic, senders=min(k, traffic["senders"])))]


class Wallet(node_drive.PushClient):
    """``PushClient`` watching several accounts."""

    def __init__(self, port: int, accounts: list[str], difficulty: int):
        super().__init__(port, accounts[0], difficulty)
        self._args = (node_drive.LOCAL, port, list(accounts), difficulty)


async def read_pool(port: int, difficulty: int) -> list[bytes]:
    """Every pending transfer's wire bytes, paged over ``GETMEMPOOL`` as a
    syncing peer reads them."""
    from p1_tpu.node import client, protocol
    from p1_tpu.node.protocol import MsgType

    out: list[bytes] = []
    async with client._session(node_drive.LOCAL, port, difficulty) as (reader, writer, _):
        cursor = None
        while True:
            await protocol.write_frame(writer, protocol.encode_getmempool(cursor))
            mtype, body = await client._read_msg(reader, writer)
            while mtype is not MsgType.MEMPOOL:
                mtype, body = await client._read_msg(reader, writer)
            more, txs = body
            out += [tx.serialize() for tx in txs]
            if not more or not txs:
                return out
            cursor = (txs[-1].fee, txs[-1].txid())


def drive(cell, traffic, seconds, *, rehearse, t_process, trace_dir, patch, meter):
    return asyncio.run(_drive(cell, traffic, seconds, rehearse=rehearse, t_process=t_process,
                              trace_dir=trace_dir, patch=patch, meter=meter))


async def _drive(cell, traffic, seconds, *, rehearse, t_process, trace_dir, patch, meter):
    import jax
    from p1_tpu.chain import ChainStore
    from p1_tpu.node import client

    t = time.perf_counter()
    data, built = load_backlog(cell.root, traffic)
    d = traffic["difficulty"]
    loop = asyncio.get_running_loop()
    with tempfile.TemporaryDirectory(prefix="perfbench-") as tmp:
        store = f"{tmp}/chain.dat"
        pathlib.Path(f"{store}.mempool").write_bytes(data)
        node, account = node_drive.build_node(cell, traffic["chain"], store, rehearse, d)
        if node.config.max_block_txs != traffic["block_txs"]:
            # Rehearsal sizes only: the chip's cell runs the node's default.
            node.config = dataclasses.replace(node.config, max_block_txs=traffic["block_txs"])
        win = Window(d, [], batch=getattr(node.miner.backend, "batch", None), backlog=data,
                     backlog_s=time.perf_counter() - t, backlog_built=built,
                     block_txs=node.config.max_block_txs,
                     watched=[account] + hottest(traffic, traffic["watched"]))
        win.phases["backlog"] = time.perf_counter() - t_process
        restore = node._load_mempool

        def restore_and_count():
            restore()
            win.restored = len(node.mempool)

        node._load_mempool = restore_and_count
        for name in ("expire", "snapshot"):
            setattr(node.mempool, name, _timed(getattr(node.mempool, name), name, win.pauses))
        started = []

        def on_gc(phase, info):
            if phase == "start":
                started[:] = [time.perf_counter()]
            elif started:
                win.pauses.append((f"gc{info['generation']}", started[0],
                                   time.perf_counter() - started[0]))

        gc.callbacks.append(on_gc)
        if patch is not None:
            patch(node)
        win.phases["node_built"] = time.perf_counter() - t_process
        rec = node_drive.SealRecorder(node.miner, loop, trace=trace_dir is not None)
        win.seals = rec.seals
        tracing = False
        await node.start()
        win.phases["node_started"] = time.perf_counter() - t_process
        push = Wallet(node.port, win.watched, d)
        push_task = asyncio.ensure_future(push.run())
        try:
            sealed = lambda ss: [s for s in ss if s.sealed is not None]  # noqa: E731
            await rec.until(lambda ss: len(sealed(ss)) >= traffic["warmup_blocks"], 120,
                            "warm-up blocks")
            await push.until(lambda ev: len(ev) >= 1, 60, "first push event")
            win.phases["warmed"] = time.perf_counter() - t_process
            if trace_dir is not None:
                # As driver ``mine``: the device's ops and the host's
                # annotations, no Python tracer.
                options = jax.profiler.ProfileOptions()
                options.host_tracer_level = 1
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                tracing = True
            n = len(rec.seals)
            await rec.until(lambda ss: sealed(ss[n:]), 60, "the seal that opens the window")
            win.first = next(s.index for s in rec.seals[n:] if s.sealed is not None)
            t_start = rec.seals[win.first].t1
            win.setup_s = t_start - t_process
            c_start = meter.compiles
            closing = node_drive._FirstSealAfter(win.first + 1, t_start + seconds)
            await rec.until(closing, seconds + 60, "the seal that closes the window")
            win.last = closing.index
            win.compiles_in_window = meter.compiles - c_start
            last_hash = rec.seals[win.last].sealed.block_hash()
            try:
                await push.until(lambda ev: any(e[2] == last_hash for e in ev),
                                 max(10.0, seconds), "push of the window's last block")
            except node_drive.Stalled as exc:
                win.stalls.append(str(exc))
        except node_drive.Stalled as exc:
            win.stalls.append(str(exc))
        finally:
            if tracing:
                jax.profiler.stop_trace()
            gc.callbacks.remove(on_gc)
            await node.stop_mining()
            with contextlib.suppress(node_drive.Stalled):
                await rec.until(lambda ss: not rec.busy, 60, "in-flight search")
            try:
                win.served = await client.get_headers(node_drive.LOCAL, node.port, d, timeout=60)
            except (OSError, asyncio.TimeoutError, ValueError) as exc:
                win.stalls.append(f"served headers: {exc!r}")
            try:
                win.pool = await asyncio.wait_for(read_pool(node.port, d), 120)
            except (OSError, asyncio.TimeoutError, ValueError) as exc:
                win.stalls.append(f"pool over the wire: {exc!r}")
            push_task.cancel()
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await push_task
            win.events = list(push.events)
            win.coalesced = node.subscriptions.events_coalesced
            win.chain = node.chain
            snap = node.telemetry_snapshot()
            win.series = {
                kind: {k: v for k, v in snap[kind].items() if k.startswith(SERIES)}
                for kind in ("counters", "gauges")}
            win.series["histograms"] = {
                k: {"count": h["count"], "sum": h["sum"]}
                for k, h in snap["histograms"].items() if k.startswith(SERIES)}
            await node.stop()
        kept = ChainStore(store)
        try:
            blocks = kept.load_blocks()
            if blocks:
                chain = kept.load_chain(d, blocks, trusted=True)
                win.blocks_stored = list(chain.main_chain())
        finally:
            kept.close()
        win.stored = [b.header for b in win.blocks_stored]
    if tracing and win.first is not None and win.last is not None:
        tr = trace.load(trace_dir)
        reduced = trace.reduce(tr, SPAN, win.first, win.last, IDLE)
        win.span_report = spans.report(tr, reduced, spans.load(trace_dir), win.first, win.last,
                                       len(win.blocks))
    return win


def _timed(fn, name: str, log: list):
    def timed(*args, **kwargs):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            log.append((name, t0, time.perf_counter() - t0))

    return timed


def verify_signature(pubkey: bytes, sig: bytes, message: bytes) -> bool:
    """Ed25519 (RFC 8032) by the ``cryptography`` wheel, which none of the
    node's caches or batches stand in front of here; by the program's
    pure-Python rung, ~40 times slower, where the wheel is absent."""
    try:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey
    except ImportError:
        from p1_tpu.core import _ed25519

        return _ed25519.verify(pubkey, sig, message)
    try:
        Ed25519PublicKey.from_public_bytes(pubkey).verify(sig, message)
    except (InvalidSignature, ValueError):
        return False
    return True


def push_txids(txs: list, watched: list[str]) -> tuple:
    """What the push plane owes a wallet watching ``watched`` for a block
    of ``txs``: for each watched account in turn, the txids of the
    block's transactions that send from or pay to it, in block order,
    each once."""
    out = []
    for account in watched:
        out += [tx.txid for tx in txs if account in (tx.sender, tx.recipient)]
    return tuple(dict.fromkeys(out))


def compare(win, seed, devices, traffic):
    """(checks, attempted, failed), each check's limit 0.  Driver
    ``mine``'s (``perfbench/checks.py``: ``pow_fail``, ``not_first``,
    ``chain_fail``, ``store_diff``, ``push_diff``, ``stalls``), and, with
    the stored chain walked from genesis through ``ledger_ref``:

    - ``composition_diff``: blocks whose transfers, in order, differ from
      the selection rule's over the backlog less what the blocks before
      confirmed;
    - ``not_full``: window blocks with fewer than ``block_txs - 1``
      transfers;
    - ``ledger_diff``: blocks that break the reference ledger's rules, and
      accounts whose balance or nonce at the node after the run differs
      from the reference's after the stored chain;
    - ``merkle_fail``: blocks whose header's merkle root is not that of
      their transactions;
    - ``sig_fail``: of ``checked_signatures`` confirmed transfers drawn
      from the seed, those whose tag is not the chain's genesis, whose
      sender is not its key's account, or whose signature fails
      ``verify_signature``, one at a time;
    - ``double_confirm``: transfers confirmed more than once;
    - ``pool_diff``: transfers in the node's pool after the run (read over
      the wire) and not in the backlog less the confirmed, and the other
      way round;
    - ``restore_missing``: backlog records the node did not restore, and
      the kept backlog's distance from ``backlog_txs`` records;
    - ``push_txids_diff``: window blocks whose push event lacks or holds
      other txids than the wallet's accounts are owed.
    """
    batch, unroll = mine.SCAN[devices[0].platform]
    scan = reference.FirstHitScan(devices, batch, unroll)
    found, attempted, failed = checks.compare(win, seed, scan, traffic["first_hit_sample"])
    backlog = ledger_ref.parse_pool_file(win.backlog)
    pending = ledger_ref.pending_by_sender(backlog)
    tag = reference.sha256d(reference.genesis(win.difficulty))
    in_window = {reference.sha256d(checks.raw(s.sealed)) for s in win.blocks}
    told = {}
    for _, h, _, _, txids in win.events:
        told.setdefault(h, txids)
    led = ledger_ref.Ledger()
    bad = {name: [] for name in ("composition_diff", "not_full", "ledger_diff", "merkle_fail",
                                 "push_txids_diff")}
    confirmed: list = []
    seen: set = set()
    double = 0
    for height, block in enumerate(win.blocks_stored[1:], 1):
        txs = [ledger_ref.parse_tx(tx.serialize())[0] for tx in block.txs]
        bhash = reference.sha256d(checks.raw(block.header))
        want = ledger_ref.select(pending, led.nonce, led.balance, win.block_txs - 1)
        if [tx.txid for tx in txs[1:]] != [tx.txid for tx in want]:
            bad["composition_diff"].append(bhash)
        if bhash in in_window:
            if len(txs) - 1 < win.block_txs - 1:
                bad["not_full"].append(bhash)
            if told.get(height) != push_txids(txs, win.watched):
                bad["push_txids_diff"].append(bhash)
        if ledger_ref.merkle_root([tx.txid for tx in txs]) != block.header.merkle_root:
            bad["merkle_fail"].append(bhash)
        if led.apply(txs, height):
            bad["ledger_diff"].append(bhash)
        for tx in txs[1:]:
            double += tx.txid in seen
            seen.add(tx.txid)
            confirmed.append(tx)
            seqs = pending.get(tx.sender)
            if seqs is not None:
                seqs.pop(tx.seq, None)
                if not seqs:
                    del pending[tx.sender]
    accounts = set(led.balances) | set(led.nonces) | {tx.sender for tx in backlog}
    if win.chain is None:
        accounts_off = len(accounts)
    else:
        accounts |= set(win.chain.balances_snapshot())
        accounts_off = sum(1 for a in accounts if (led.balance(a), led.nonce(a))
                           != (win.chain.balance(a), win.chain.nonce(a)))

    k = min(traffic["checked_signatures"], len(confirmed))
    sample = random.Random(seed).sample(confirmed, k)
    sig_fail = sum(1 for tx in sample
                   if tx.chain != tag or ledger_ref.account_of(tx.pubkey) != tx.sender
                   or not verify_signature(tx.pubkey, tx.sig, tx.signed))
    left = {tx.txid for tx in backlog} - seen
    if win.pool is None:
        pool_diff = len(left)
    else:
        held = {ledger_ref.parse_tx(raw)[0].txid for raw in win.pool}
        pool_diff = len(left ^ held)
    restore_missing = abs(traffic["backlog_txs"] - (win.restored or 0)) + abs(
        traffic["backlog_txs"] - len(backlog))
    values = {
        "composition_diff": len(bad["composition_diff"]),
        "not_full": len(bad["not_full"]),
        "ledger_diff": len(bad["ledger_diff"]) + accounts_off,
        "merkle_fail": len(bad["merkle_fail"]),
        "sig_fail": sig_fail,
        "double_confirm": double,
        "pool_diff": pool_diff,
        "restore_missing": restore_missing,
        "push_txids_diff": len(bad["push_txids_diff"]),
    }
    found.update({name: {"value": v, "limit": 0} for name, v in values.items()})
    wrong = {h for hs in bad.values() for h in hs} & in_window
    return found, attempted, min(attempted, failed + len(wrong))


@dataclasses.dataclass
class View(node_view.View):
    spans_ms_per_block: dict | None = None  # p1.* span -> ms a window block, traced runs


def view(win, cell, reduced, peak):
    base = node_view.View.of(win, cell, reduced, peak)
    fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
    report = win.span_report
    return View(**fields, spans_ms_per_block=report["spans_ms_per_block"] if report else None)


def facts(win) -> dict:
    from p1_tpu.core import keys

    inside = {reference.sha256d(checks.raw(s.sealed)) for s in win.blocks}
    out = {
        "blocks": len(win.blocks),
        "first_nonces": [h.nonce for h in win.served[1:6]],
        "height": len(win.served) - 1,
        "batch": win.batch,
        "hashes_done_gap": mine.hashes_done_gap(win),
        "push_coalesced": win.coalesced,
        "backlog_s": win.backlog_s,
        "backlog_built": win.backlog_built,
        "sign_rung": "cryptography" if keys.HAVE_CRYPTOGRAPHY else "pure-python",
        "verify_rung": keys.backend(),
        "restored": win.restored,
        "window_transfers": sum(len(b.txs) - 1 for b in win.blocks_stored
                                if reference.sha256d(checks.raw(b.header)) in inside),
        "pool_left": None if win.pool is None else len(win.pool),
        "program": win.series,
    }
    if win.blocks:
        t0, t1 = win.seals[win.first].t1, win.seals[win.last].t1
        inside = win.seals[win.first:win.last + 1]
        out["host_gaps_ms"] = [round((b.t0 - a.t1) * 1e3, 2) for a, b in zip(inside, inside[1:])]
        # What ``hashes_per_s`` would read here; the cell does not report it
        # (its one-chip runs spread wider than half that metric's bound).
        out["useful_hashes_per_s"] = sum(map(node_view.useful_hashes, inside[1:])) / (t1 - t0)
        # Young-generation collections under 5 ms are many and cost little.
        out["pauses_in_window"] = [[what, round(t - t0, 3), round(d * 1e3, 2)]
                                   for what, t, d in win.pauses
                                   if t0 <= t < t1 and (d > 0.005 or what != "gc0")]
    if win.span_report is not None:
        out["spans"] = win.span_report
    return out


def control(node) -> None:
    """The guarantee that a block carries exactly what the selection rule
    picks, broken: each template leaves out its last transfer (its merkle
    root recomputed).  A valid block, a little cheaper to assemble and
    connect, that every other layer takes as usual; only
    ``composition_diff`` and ``not_full`` can see it."""
    from p1_tpu.core import Block, BlockHeader, merkle_root

    assemble = node._assemble

    def short() -> Block:
        block = assemble()
        if len(block.txs) < 2:
            return block
        txs = block.txs[:-1]
        h = block.header
        header = BlockHeader(h.version, h.prev_hash, merkle_root([tx.txid() for tx in txs]),
                             h.timestamp, h.difficulty, h.nonce)
        return Block(header, txs)

    node._assemble = short
