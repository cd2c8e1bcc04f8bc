"""Mining-path spans and search counters.

Registry spans write a profiler twin (``p1.`` + the histogram's name
without ``_s``) beside their histogram; the device-stepped backends count
their launches in ``MineStats`` and write ``p1.search.*`` spans; the node
folds the counters into its registry and tags one block's spans with its
height.  A node with telemetry off writes none of them.
"""

from __future__ import annotations

import asyncio
import glob
import random
import subprocess
import sys
import warnings

import pytest

from p1_tpu.core import BlockHeader
from p1_tpu.hashx import get_backend
from p1_tpu.node.telemetry import MetricsRegistry, profiler_name

jax = pytest.importorskip("jax")

from p1_tpu.hashx.jax_backend import JaxBackend  # noqa: E402
from p1_tpu.miner import Miner  # noqa: E402


def _capture(tmp_path, body):
    """Run ``body()`` under a CPU profiler trace; every ``p1.*`` host
    event as ``(name, start_ns, end_ns, stats)``, by start."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("p1."):
                        end = e.start_ns + e.duration_ns
                        events.append((e.name, e.start_ns, end, dict(e.stats)))
    return sorted(events, key=lambda e: e[1])


def _header(seed: int, difficulty: int) -> BlockHeader:
    rng = random.Random(seed)
    return BlockHeader(1, rng.randbytes(32), rng.randbytes(32), 1735689700, difficulty, 0)


class TestRegistryTwin:
    def test_profiler_name_rule(self):
        assert profiler_name("stage.store_s") == "p1.stage.store"
        assert profiler_name("mine.post_seal_s") == "p1.mine.post_seal"
        assert profiler_name("wire") == "p1.wire"

    @pytest.mark.parametrize("enabled", [True, False])
    def test_span_twin_only_when_enabled(self, tmp_path, enabled):
        reg = MetricsRegistry(enabled=enabled)

        def body():
            with reg.span("stage.store_s", height=5):
                with reg.twin("stage.relay_s", height=5):
                    pass

        events = _capture(tmp_path, body)
        if enabled:
            assert [(n, s) for n, _, _, s in events] == [
                ("p1.stage.store", {"height": 5}),
                ("p1.stage.relay", {"height": 5}),
            ]
            store, relay = events
            assert store[1] <= relay[1] and relay[2] <= store[2]  # nested
            assert reg.histograms["stage.store_s"].count == 1
            assert "stage.relay_s" not in reg.histograms  # twin alone
        else:
            assert events == []
            assert reg.histograms == {}

    def test_telemetry_never_loads_jax(self):
        code = (
            "import sys\n"
            "from p1_tpu.node.telemetry import MetricsRegistry\n"
            "reg = MetricsRegistry()\n"
            "with reg.span('stage.store_s', height=1): pass\n"
            "assert reg.histograms['stage.store_s'].count == 1\n"
            "assert 'jax' not in sys.modules\n"
        )
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr


class TestMineStatsCounters:
    """The counters are exact on the CPU ``jax`` backend."""

    def _ramping_backend(self):
        be = JaxBackend(batch=4096)  # a fresh instance: ramp_floor is set on it
        be.ramp_floor = 512
        return be

    def test_hit_in_the_opening_step_abandons_the_step_in_flight(self):
        # The first hit below the ramp floor: the opening 512-nonce step
        # holds it, and the 4096-nonce step dispatched behind it is never
        # read back.
        cpu = get_backend("cpu")
        header = next(h for h in (_header(s, 8) for s in range(200))
                      if cpu.search(h.mining_prefix(), 0, 512, 8).nonce is not None)
        miner = Miner(backend=self._ramping_backend(), chunk=1 << 15)
        sealed = miner.search_nonce(header)
        assert sealed.nonce == cpu.search(header.mining_prefix(), 0, 512, 8).nonce
        s = miner.last_stats
        assert (s.launches, s.nonces_launched) == (2, 512 + 4096)
        assert (s.launches_abandoned, s.nonces_abandoned) == (1, 4096)
        # Chained to the hit, the step in flight skipped its work.
        assert (s.launches_skipped, s.nonces_skipped) == (1, 4096)
        assert s.prepare_s > 0 and s.dispatch_s > 0 and s.readback_s > 0
        assert s.hashes_done == sealed.nonce + 1

    def test_resumed_scan_drains_its_final_step(self):
        # No ramp from a nonzero start; no hit at difficulty 255, so the
        # scan runs to the end of nonce space and reads every step back.
        header = _header(1, 255)
        miner = Miner(backend=self._ramping_backend(), chunk=1 << 15,
                      max_timestamp_rolls=0)
        assert miner.search_nonce(header, start_nonce=(1 << 32) - 3 * 4096) is None
        s = miner.last_stats
        assert (s.launches, s.nonces_launched) == (3, 3 * 4096)
        assert (s.launches_abandoned, s.nonces_abandoned) == (0, 0)
        assert (s.launches_skipped, s.nonces_skipped) == (0, 0)
        assert s.hashes_done == 3 * 4096

    def test_search_result_carries_the_counts(self):
        be = self._ramping_backend()
        res = be.search(_header(2, 255).mining_prefix(), 4096, 2 * 4096, 255)
        assert res.nonce is None and res.steps.launches == 2

    @pytest.mark.parametrize("name", ["cpu", "numpy"])
    def test_backends_without_steps_count_nothing(self, name):
        miner = Miner(backend=get_backend(name), chunk=1 << 12)
        assert miner.search_nonce(_header(3, 6)) is not None
        s = miner.last_stats
        assert s.hashes_done > 0
        assert (s.launches, s.nonces_launched, s.launches_abandoned) == (0, 0, 0)
        assert (s.prepare_s, s.dispatch_s, s.readback_s) == (0.0, 0.0, 0.0)


def _mine_traced(tmp_path, telemetry: bool, blocks: int = 3):
    """A node on the CPU ``jax`` backend (ramping, so steps are abandoned)
    mines ``blocks`` blocks under a profiler trace."""
    from p1_tpu.config import NodeConfig
    from p1_tpu.node import Node

    be = JaxBackend(batch=4096)
    be.ramp_floor = 512
    node = Node(NodeConfig(difficulty=10, backend="jax", host="127.0.0.1", port=0,
                           telemetry=telemetry),
                miner=Miner(backend=be, chunk=1 << 14))

    async def main():
        await node.start()
        try:
            for _ in range(600):
                if node.chain.height >= blocks:
                    break
                await asyncio.sleep(0.05)
        finally:
            await node.stop_mining()
            await node.stop()

    events = _capture(tmp_path, lambda: asyncio.run(main()))
    assert node.chain.height >= blocks
    return node, events


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class TestNodeTrace:
    def test_mined_blocks_carry_nested_spans_and_counters(self, tmp_path):
        node, events = _mine_traced(tmp_path, telemetry=True)
        by_name: dict[str, list] = {}
        for e in events:
            by_name.setdefault(e[0], []).append(e)
        for name in ("p1.mine.assemble", "p1.mine.search", "p1.mine.post_seal",
                     "p1.search.prepare", "p1.search.dispatch", "p1.search.readback",
                     "p1.stage.validate", "p1.stage.store", "p1.push.notify"):
            assert by_name.get(name), name
        # One block's spans share its height; the backend's spans lie
        # inside the search of that height, the block stages inside its
        # post-seal.  The search that the node's stop cut short has no
        # post-seal, and its counters were never folded in.
        searches = {e[3]["height"]: e for e in by_name["p1.mine.search"]}
        sealed = {e[3]["height"]: e for e in by_name["p1.mine.post_seal"]}
        assert set(sealed) <= set(searches)
        done = [searches[h] for h in sealed]
        step_spans = {}
        for name in ("p1.search.prepare", "p1.search.dispatch", "p1.search.readback"):
            step_spans[name] = [e for e in by_name[name]
                                if any(_within(e, s) for s in done)]
            assert len(step_spans[name]) >= len(done), name
            cut = max(s[2] for s in done)
            assert all(e in step_spans[name] for e in by_name[name] if e[1] < cut), name
        for name in ("p1.stage.validate", "p1.stage.store", "p1.push.notify"):
            for e in by_name[name]:
                assert _within(e, sealed[e[3]["height"]]), (name, e)
        for h, s in sealed.items():
            assert searches[h][2] <= s[1]  # post-seal follows the seal
        # Every launch has an id and a span; the ids not read back are the
        # abandoned launches, which the registry counted.
        launched = {e[3]["launch"]: e[3]["nonces"] for e in step_spans["p1.search.dispatch"]}
        read = {e[3]["launch"] for e in step_spans["p1.search.readback"]}
        assert read <= set(launched) and len(read) == len(step_spans["p1.search.readback"])
        counters = node.telemetry_snapshot()["counters"]
        assert counters["search.launches"] == len(launched)
        assert counters["search.nonces_launched"] == sum(launched.values())
        assert counters["search.launches_abandoned"] == len(set(launched) - read) > 0
        assert counters["search.nonces_abandoned"] == sum(
            n for i, n in launched.items() if i not in read)
        assert counters["search.launches_skipped"] == counters["search.launches_abandoned"]
        assert counters["search.nonces_skipped"] == counters["search.nonces_abandoned"]
        hists = node.telemetry_snapshot()["histograms"]
        for name in ("mine.assemble_s", "mine.search_s", "mine.post_seal_s", "push.notify_s",
                     "search.prepare_s", "search.dispatch_s", "search.readback_s"):
            assert hists[name]["count"] >= 1, name

    def test_telemetry_off_writes_no_span_and_no_new_series(self, tmp_path):
        node, events = _mine_traced(tmp_path, telemetry=False, blocks=2)
        assert events == []
        snap = node.telemetry_snapshot()
        assert not [n for n in snap["counters"] if n.startswith("search.")]
        assert snap["histograms"] == {}


class TestReplayPackSpan:
    """``replay_device`` wraps its gather of the header objects in one
    ``p1.replay.pack`` span a call: the headers gathered, and how many had
    no cached encoding and were serialized."""

    @pytest.fixture(scope="class")
    def parsed(self):
        from p1_tpu.chain import generate_headers, parse_headers

        return parse_headers(b"".join(h.serialize() for h in generate_headers(20, 4)))

    def test_pack_span_counts_headers_and_fallback(self, tmp_path, parsed):
        import dataclasses

        from p1_tpu.chain import replay_device

        fresh = [dataclasses.replace(h) for h in parsed[:7]] + parsed[7:]
        events = _capture(tmp_path, lambda: [
            replay_device(chain, segment=8) for chain in (parsed, fresh)])
        packs = [e[3] for e in events if e[0] == "p1.replay.pack"]
        assert packs == [{"headers": 20, "serialized": 0}, {"headers": 20, "serialized": 7}]

    def test_step_spans_off_writes_no_pack_span(self, tmp_path, parsed):
        from p1_tpu.chain import replay_device
        from p1_tpu.hashx.backend import step_spans

        def body():
            with step_spans(False):
                assert replay_device(parsed, segment=8).valid

        assert _capture(tmp_path, body) == []


def _pool_file(store: str, difficulty: int, senders: int = 4, per: int = 6,
               unaffordable: int = 2) -> tuple[int, int]:
    """Write ``<store>.mempool``: ``senders * per`` zero-value transfers,
    seqs gap-free from 0, and ``unaffordable`` more that pay a fee their
    empty accounts cannot cover.  Returns (restorable, unaffordable)."""
    from p1_tpu.core.genesis import make_genesis
    from p1_tpu.core.keys import Keypair
    from p1_tpu.core.tx import Transaction
    from p1_tpu.mempool import dump_mempool

    tag = make_genesis(difficulty).block_hash()
    keys = [Keypair.from_seed_text(f"pool-spans/{i}") for i in range(senders)]
    rows = [(Transaction.transfer(keys[i % senders], keys[(i + 1) % senders].account, 0, 0,
                                  i // senders, chain=tag), 0.0)
            for i in range(senders * per)]
    poor = Keypair.from_seed_text("pool-spans/poor")
    rows += [(Transaction.transfer(poor, keys[0].account, 0, 1, i, chain=tag), 0.0)
             for i in range(unaffordable)]
    with open(f"{store}.mempool", "wb") as fh:
        fh.write(dump_mempool(rows))
    return senders * per, unaffordable


def _pool_node(tmp_path, telemetry: bool, max_block_txs: int = 4, difficulty: int = 8):
    from p1_tpu.config import NodeConfig
    from p1_tpu.node import Node

    store = str(tmp_path / "chain.dat")
    counts = _pool_file(store, difficulty)
    node = Node(NodeConfig(difficulty=difficulty, backend="cpu", host="127.0.0.1", port=0,
                           store_path=store, miner_id="pool-spans-miner",
                           max_block_txs=max_block_txs, telemetry=telemetry))
    return node, counts


class TestPoolSpans:
    """Selection, eviction and restore: spans with the block's height, and
    counters; recording changes nothing the pool does."""

    def test_spans_nest_in_their_blocks_and_counters_hold(self, tmp_path):
        from p1_tpu.config import NodeConfig
        from p1_tpu.node import Node

        store = str(tmp_path / "chain.dat")
        restorable, unaffordable = _pool_file(store, 10, per=30)
        be = JaxBackend(batch=4096)
        node = Node(NodeConfig(difficulty=10, backend="jax", host="127.0.0.1", port=0,
                               store_path=store, miner_id="pool-spans-miner", max_block_txs=4),
                    miner=Miner(backend=be, chunk=1 << 14))

        async def main():
            await node.start()
            try:
                for _ in range(600):
                    if node.chain.height >= 3:
                        break
                    await asyncio.sleep(0.05)
            finally:
                await node.stop_mining()
                snap = node.telemetry_snapshot()
                await node.stop()
            return snap

        out = {}
        events = _capture(tmp_path / "trace", lambda: out.setdefault("snap", asyncio.run(main())))
        snap = out["snap"]
        assert node.chain.height >= 3
        by_name: dict[str, list] = {}
        for e in events:
            by_name.setdefault(e[0], []).append(e)
        (restore,) = by_name["p1.mempool.restore"]
        assert restore[3] == {"height": 0}
        assemble = {e[3]["height"]: e for e in by_name["p1.mine.assemble"]}
        selects = {e[3]["height"]: e for e in by_name["p1.mempool.select"]}
        assert set(selects) == set(assemble) and restore[2] <= min(s[1] for s in selects.values())
        for h, e in selects.items():
            assert _within(e, assemble[h]), h
        sealed = {e[3]["height"]: e for e in by_name["p1.mine.post_seal"]}
        evicts = {e[3]["height"]: e for e in by_name["p1.mempool.evict"]}
        assert set(evicts) == set(sealed) and len(evicts) >= 3
        for h, e in evicts.items():
            assert _within(e, sealed[h]), h
        assert snap["counters"]["mempool.restored"] == restorable
        assert snap["counters"]["mempool.restore_dropped"] == unaffordable
        mined = [b for b in node.chain.main_chain()][1:]
        assert [len(b.txs) for b in mined[:3]] == [4, 4, 4]
        assert snap["gauges"]["mine.block_txs"] == len(mined[-1].txs) - 1 == 3
        assert snap["gauges"]["mempool.pending"] == len(node.mempool) == \
            restorable - 3 * len(mined)
        hists = snap["histograms"]
        assert hists["mempool.select_s"]["count"] == len(selects)
        assert hists["mempool.evict_s"]["count"] == len(evicts)
        assert hists["mempool.restore_s"]["count"] == 1

    @pytest.mark.parametrize("telemetry", [True, False])
    def test_restored_counts_the_records_restored(self, tmp_path, telemetry):
        node, (restorable, unaffordable) = _pool_node(tmp_path, telemetry)
        node._load_mempool()
        counters = node.telemetry_snapshot()["counters"]
        assert counters["mempool.restored"] == len(node.mempool) == restorable
        assert counters["mempool.restore_dropped"] == unaffordable
        assert ("mempool.restore_s" in node.telemetry.histograms) is telemetry

    def test_select_and_eviction_are_the_same_with_telemetry_off(self, tmp_path):
        from p1_tpu.core import Block

        def mine(telemetry: bool) -> list:
            node, _ = _pool_node(tmp_path / str(telemetry), telemetry)
            node._load_mempool()
            seen = []

            async def main():
                for height in range(1, 5):
                    candidate = node._assemble()
                    sealed = node.miner.search_nonce(candidate.header)
                    await node._handle_block(Block(sealed, candidate.txs), origin=None,
                                             height=height)
                    pool = sorted(tx.serialize() for tx in node.mempool._txs.values())
                    seen.append(([tx.serialize() for tx in candidate.txs], pool))

            asyncio.run(main())
            assert node.chain.height == 4
            return seen

        (tmp_path / "True").mkdir()
        (tmp_path / "False").mkdir()
        on, off = mine(True), mine(False)
        assert on == off
        assert [len(txs) for txs, _ in on] == [4, 4, 4, 4]
        assert [len(pool) for _, pool in on] == [21, 18, 15, 12]
