"""Telemetry overhead benchmark: what the stage spans cost the hot path.

The round-14 telemetry plane (p1_tpu/node/telemetry.py) instruments the
block pipeline — wire frame -> admission -> validation -> store append ->
relay — as clock-seam spans.  Observability that slows the system it
observes is a tax nobody audited, so this harness measures exactly that:
the SAME block stream driven through a real ``Node``'s ``_dispatch``
front door (decode, governor admission, add_block, store append, relay
encode — everything a gossip frame pays) with telemetry enabled and
disabled, best-of-N each, on one JSON line.

``--spans`` times instead what the mining path's spans cost, with no
profiler trace recording and with one: a registry span, a twin alone, a
host-loop annotation, per call and per solo-d20 block (``span_cost``).

It also emits the per-stage latency table (p50/p95/p99 from the enabled
run's histograms) — the figure docs/PERF.md's "Telemetry plane" section
records from a 10k-block run, and the ROADMAP-2 pipeline split will be
scoped against.

Same contract as bench.py: measured on this machine, no estimates.
Difficulty 1 keeps mining the fixtures cheap while the PoW checks stay
real; signature memos are warmed first (the mempool-admission state a
steady-state block meets), so the measured plane is
serialization/validation/bookkeeping, not Ed25519.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import tempfile
import time
from pathlib import Path

# Runnable as `python benchmarks/telemetry_overhead.py` from a checkout.
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


class _BenchPeer:
    """The minimal peer surface ``_dispatch``/``_handle_block`` touch on
    the ingest path: a label, a host, and a real governor budget."""

    label = "bench"
    host = "127.0.0.1"
    mempool_inflight_since = None

    def __init__(self, node):
        self.budget = node.governor.budget()


def _build_frames(n_blocks: int, txs: int, difficulty: int):
    """Wire BLOCK frames (unstamped) for a freshly mined chain, plus the
    chain itself so callers can warm signature memos."""
    from benchmarks.host_ingest import build_blocks

    from p1_tpu.node import protocol
    from p1_tpu.core.block import Block

    chain, raws = build_blocks(n_blocks, txs, difficulty)
    frames = [
        protocol.encode_block(Block.deserialize(raw)) for raw in raws
    ]
    return chain, frames


_ROUND = 0


def _make_node(blocks, difficulty: int, telemetry: bool, tmpdir):
    """A fresh node over a fresh on-disk store, its verify-once
    signature cache seeded with the fixture chain's (known-valid — we
    mined it) signatures: a steady-state node has already verified
    every transfer a block carries at mempool admission, and this
    harness measures the serialization/validation/bookkeeping plane
    (host_ingest.py's contract), not cold Ed25519 — which on the
    wheel-less host would drown the span overhead it exists to
    expose."""
    global _ROUND
    from p1_tpu.chain.store import ChainStore
    from p1_tpu.config import NodeConfig
    from p1_tpu.node.node import Node

    _ROUND += 1
    store = ChainStore(Path(tmpdir) / f"tel_{_ROUND}.chain", fsync=False)
    node = Node(
        NodeConfig(
            difficulty=difficulty,
            mine=False,
            mempool_ttl_s=0.0,
            telemetry=telemetry,
        ),
        store=store,
    )
    for blk in blocks:
        for tx in blk.txs:
            if not tx.is_coinbase:
                node.sig_cache.add(tx.txid(), tx.pubkey, tx.sig)
    store.acquire()
    return node


def paired_round(frames, blocks, difficulty: int, tmpdir):
    """One pass of the block stream through TWO nodes — telemetry off
    and on — dispatching each frame to both back to back, per-frame
    timed, the first-dispatcher alternating per frame.

    Why this shape: on this host identical whole-stream rounds swing
    ±20% (CPU-quota throttling oscillates at the same timescale as a
    round), so any round-level A/B measures the environment, not the
    spans — the round-14 ledger records two failed cuts.  Frame-level
    interleaving puts both variants microseconds apart inside every
    throttle window, and alternating who goes first cancels the
    cache-warming the first dispatcher does for the second (both nodes
    decode the same frame bytes).  Returns (bps_off, bps_on, node_on).
    """
    node_off = _make_node(blocks, difficulty, False, tmpdir)
    node_on = _make_node(blocks, difficulty, True, tmpdir)

    async def _run():
        peer_off = _BenchPeer(node_off)
        peer_on = _BenchPeer(node_on)
        dts_off = []
        dts_on = []
        perf = time.perf_counter
        for i, frame in enumerate(frames):
            if i % 2 == 0:
                a = perf()
                await node_off._dispatch(peer_off, frame)
                b = perf()
                await node_on._dispatch(peer_on, frame)
                c = perf()
                dts_off.append(b - a)
                dts_on.append(c - b)
            else:
                a = perf()
                await node_on._dispatch(peer_on, frame)
                b = perf()
                await node_off._dispatch(peer_off, frame)
                c = perf()
                dts_on.append(b - a)
                dts_off.append(c - b)
        return dts_off, dts_on

    try:
        dts_off, dts_on = asyncio.run(_run())
    finally:
        node_off.store.close()
        node_on.store.close()
    for node in (node_off, node_on):
        assert node.chain.height == len(frames), (
            node.chain.height,
            len(frames),
        )
    # Medians, not sums: a handful of kernel-writeback (or throttle)
    # stalls land on random frames and would skew a sum by whole
    # percents; the per-frame median is immune to them, and the paired
    # per-frame DIFFERENCE median cancels content variation too.
    dts_off.sort()
    dts_on.sort()
    med_off = dts_off[len(dts_off) // 2]
    med_on = dts_on[len(dts_on) // 2]
    return 1.0 / med_off, 1.0 / med_on, node_on


def _stage_table(node) -> dict:
    """{stage: {count, p50_ms, p95_ms, p99_ms}} from the node's
    registry — the PERF.md per-stage latency rows."""
    out = {}
    for name in (
        "stage.frame_s",
        "stage.admission_s",
        "stage.validate_s",
        "stage.store_s",
        "stage.relay_s",
    ):
        h = node.telemetry.histograms.get(name)
        if h is None or h.count == 0:
            continue
        out[name] = {
            "count": h.count,
            "p50_ms": round(1e3 * h.percentile(50), 4),
            "p95_ms": round(1e3 * h.percentile(95), 4),
            "p99_ms": round(1e3 * h.percentile(99), 4),
        }
    return out


def bench_quick(blocks: int = 300, txs: int = 2, repeats: int = 3) -> dict:
    """The bench.py entry: small run, same shape as main()'s output.

    One discarded warmup round, then ``repeats`` frame-interleaved
    paired rounds (see ``paired_round`` for why round-level A/B is
    unmeasurable on this host); the overhead figure is the median of
    the per-round on/off ratios."""
    difficulty = 1
    chain, frames = _build_frames(blocks, txs, difficulty)
    # main_chain() yields lazily — materialize, or the first seeding
    # pass would exhaust it and every later node would run cache-cold.
    fixture_blocks = list(chain.main_chain())
    ratios = []
    bps_off = bps_on = 0.0
    node = None
    with tempfile.TemporaryDirectory() as tmpdir:
        paired_round(frames, fixture_blocks, difficulty, tmpdir)  # warmup
        for _ in range(repeats):
            off, on, node = paired_round(
                frames, fixture_blocks, difficulty, tmpdir
            )
            ratios.append(on / off)
            bps_off = max(bps_off, off)
            bps_on = max(bps_on, on)
    ratios.sort()
    median_ratio = ratios[len(ratios) // 2]
    return {
        "ingest_plain_bps": round(bps_off, 1),
        "ingest_telemetry_bps": round(bps_on, 1),
        "overhead_pct": round(100.0 * (1.0 - median_ratio), 2),
        "pair_ratios": [round(r, 4) for r in ratios],
        "stages": _stage_table(node),
    }


#: What one solo-d20 block writes on the mining path (PERF.md, spans
#: table): four registry spans (mine.assemble/search/post_seal,
#: push.notify), three stage twins (validate, store, relay), the host
#: loop's annotations (one prepare, 2.02 dispatches and 1.02 readbacks a
#: block at d20: the cell's launches_per_block on a v5e), eight
#: perf_counter reads around them, and the fold of the step counters.
D20_SPANS, D20_TWINS, D20_ANNOTATIONS, D20_CLOCK_READS = 4, 3, 4.04, 8


def span_cost(n: int = 200_000) -> dict:
    """Per-call cost, in microseconds, of the mining path's spans with no
    profiler trace recording (``off``) and with one recording (``on``),
    and their sum for one solo-d20 block, all of it new: the mine loop
    had no span before.  A registry span, a twin alone, a host-loop
    annotation, a clock read, and the node's fold of one search's
    counters into its registry."""
    import tempfile

    import jax  # the node's process has JAX loaded: twins are live

    from p1_tpu.config import NodeConfig
    from p1_tpu.miner import MineStats
    from p1_tpu.node.node import Node

    node = Node(NodeConfig(mine=False))
    reg = node.telemetry
    annotate = jax.profiler.TraceAnnotation
    stats = MineStats(launches=2, nonces_launched=9 << 22, launches_abandoned=1,
                      nonces_abandoned=1 << 25)

    def per_call_us(body, calls: int) -> float:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(calls):
                body(i)
            best = min(best, (time.perf_counter() - t0) / calls * 1e6)
        return best

    def span(i):
        with reg.span("mine.search_s", height=i):
            pass

    def twin(i):
        with reg.twin("stage.store_s", height=i):
            pass

    def annotation(i):
        with annotate("p1.search.dispatch", launch=i, nonces=1 << 25):
            pass

    def measure(calls: int) -> dict:
        out = {
            "span_us": per_call_us(span, calls),
            "twin_us": per_call_us(twin, calls),
            "annotation_us": per_call_us(annotation, calls),
            "clock_read_us": per_call_us(lambda i: time.perf_counter(), calls),
            "fold_us": per_call_us(lambda i: node._record_steps(stats), calls),
        }
        out["d20_block_us"] = (
            D20_SPANS * out["span_us"] + D20_TWINS * out["twin_us"]
            + D20_ANNOTATIONS * out["annotation_us"]
            + D20_CLOCK_READS * out["clock_read_us"] + out["fold_us"])
        return {k: round(v, 4) for k, v in out.items()}

    off = measure(n)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            on = measure(n // 10)  # the trace keeps every event in memory
        finally:
            jax.profiler.stop_trace()
    return {"off": off, "on": on}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--blocks", type=int, default=10_000)
    ap.add_argument("--txs", type=int, default=2, help="transfers per block")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--spans", action="store_true",
                    help="only the mining path's span cost (span_cost)")
    args = ap.parse_args(argv)

    if args.spans:
        print(json.dumps({"metric": "mining_span_cost_us", **span_cost()}))
        return 0
    out = bench_quick(args.blocks, args.txs, args.repeats)
    from p1_tpu.hashx.perf_record import RECORDED_HOST_INGEST_BPS

    print(
        json.dumps(
            {
                "metric": "telemetry_overhead_pct",
                "value": out["overhead_pct"],
                "unit": "%",
                "n_blocks": args.blocks,
                "txs_per_block": args.txs,
                "ingest_with_telemetry_vs_recorded": round(
                    out["ingest_telemetry_bps"] / RECORDED_HOST_INGEST_BPS,
                    2,
                ),
                **out,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
