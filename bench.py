"""Benchmark: SHA-256d hashes/sec/chip + time-to-block at difficulty 20.

The driver contract (run on the ambient platform — the real TPU chip when
available): print ONE JSON line with the headline metric and the speedup
over the CPU baseline.  Metrics per BASELINE.json:2 — "SHA-256d
hashes/sec/chip; time-to-block at difficulty 20" — measured, not estimated;
the ≥10x north-star target is BASELINE.json:5.

Extra keys carry the sub-measurements (cpu baseline rate, per-config
detail); the required keys stay exactly {metric, value, unit, vs_baseline}.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

# Difficulty no hash can meet: keeps throughput runs scanning the whole range.
IMPOSSIBLE = 255


def _throughput(backend, prefix: bytes, count: int, repeats: int = 3) -> float:
    """Best-of-N hashes/sec scanning ``count`` nonces with no hits."""
    backend.search(prefix, 0, min(count, 1 << 16), IMPOSSIBLE)  # warmup/compile
    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = backend.search(prefix, 0, count, IMPOSSIBLE)
        dt = time.perf_counter() - t0
        best = max(best, res.hashes_done / dt)
    return best


def _time_to_block(miner, difficulty: int, blocks: int = 5) -> float:
    """Median wall-clock seconds to seal a block at ``difficulty``."""
    from p1_tpu.core.genesis import make_genesis
    from p1_tpu.core.header import BlockHeader

    tip = make_genesis(difficulty)
    times = []
    prev = tip.block_hash()
    for height in range(1, blocks + 1):
        header = BlockHeader(
            1, prev, bytes(32), tip.header.timestamp + 60 * height, difficulty, 0
        )
        t0 = time.perf_counter()
        sealed = miner.search_nonce(header)
        times.append(time.perf_counter() - t0)
        assert sealed is not None
        prev = sealed.block_hash()
    return statistics.median(times)


def main() -> int:
    import jax

    from p1_tpu.core.genesis import make_genesis
    from p1_tpu.hashx import get_backend
    from p1_tpu.miner import Miner

    platform = jax.default_backend()
    if platform != "tpu":
        # A measurement path that finds no chip fails; it never reports a
        # CPU rate under a device metric's name.
        print(f"bench.py needs a TPU; JAX found {platform!r}", file=sys.stderr)
        return 1
    prefix = make_genesis(20).header.mining_prefix()

    # CPU baseline (the graded ratio's denominator): best-of-3 over a ≥2 s
    # window each.  Round 3 used one 0.7 s shot and the recorded ratio
    # swung 933x -> 1998x on scheduler noise alone (VERDICT r3 weak #1).
    cpu_hps = _throughput(get_backend("cpu"), prefix, 1 << 21, repeats=3)

    # Flagship: the Pallas kernel ("tpu"), with the pure-XLA formulation
    # beside it for the Pallas-vs-XLA record (docs/PERF.md): same chip,
    # same session.
    device = get_backend("tpu")
    device_hps = _throughput(device, prefix, 1 << 29)
    extra = {"xla_hps": round(_throughput(get_backend("jax"), prefix, 1 << 28))}
    from p1_tpu.hashx.native_build import NativeBuildError

    try:
        # The C++ host tier (SHA-NI when available); skipped cleanly when
        # no toolchain exists on the bench host — anything else is a real
        # regression and should crash the bench loudly.
        native = get_backend("native")
    except (NativeBuildError, OSError):
        native = None
    if native is not None:
        extra["native_hps"] = round(_throughput(native, prefix, 1 << 22, repeats=1))
        extra["native_shani"] = native.has_shani

    # Host-load context for BOTH ratios below: the live cpu denominator
    # collapses up to ~3.6x under co-tenant load (rounds 2-5 record),
    # and these figures are what lets a reader see a degraded
    # denominator instead of inferring it from a suspicious ratio.
    try:
        load_1m, load_5m, _ = os.getloadavg()
    except OSError:
        load_1m = load_5m = None

    # Peak RSS of the bench process itself: the memory-side context
    # field the governor work reads against — an unexplained jump here
    # flags a resident-set regression the throughput numbers can't see
    # (docs/PERF.md "Memory-bounded operation").  VmHWM, not ru_maxrss:
    # the latter survives fork+exec on Linux, so a bench spawned from a
    # fat driver would report the DRIVER's high-water mark
    # (benchmarks/memory_bound.py measured exactly that failure mode).
    peak_rss_bytes = None
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmHWM:"):
                    peak_rss_bytes = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    if peak_rss_bytes is None:
        import resource

        peak_rss_bytes = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        )

    ttb = _time_to_block(Miner(backend=device), difficulty=20)

    # Host ingest plane (the serialization-side headline,
    # benchmarks/host_ingest.py): a quick same-session measurement,
    # reported against the ONE recorded constant so a regression in the
    # zero-repack pipeline surfaces in the bench JSON — same convention
    # as the TPU degradation guard above.
    from p1_tpu.hashx.perf_record import (
        HOST_INGEST_DEGRADED_FRACTION,
        RECORDED_HOST_INGEST_BPS,
    )

    try:
        from benchmarks.host_ingest import bench_ingest, build_blocks

        chain, raws = build_blocks(300, 2, difficulty=1)
        for blk in chain.main_chain():
            for tx in blk.txs:
                tx.verify_signature()  # warm the memo, as ingest meets it
        ingest_bps = bench_ingest(raws, 1, repeats=3)
        extra["host_ingest_bps"] = round(ingest_bps)
        extra["host_ingest_vs_recorded"] = round(
            ingest_bps / RECORDED_HOST_INGEST_BPS, 2
        )
        if ingest_bps < HOST_INGEST_DEGRADED_FRACTION * RECORDED_HOST_INGEST_BPS:
            extra["host_ingest_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Staged node (round 19): the same ingest shape through the
    # pipeline driver (node/pipeline.py) at 1 worker, against ITS
    # recorded constant — staged ingest pays cold-cache signature math
    # and fsynced appends, so it has its own denominator; the unstaged
    # same-driver control rides along so the staging overhead is a
    # measured per-session number (docs/PERF.md "Staged node").
    from p1_tpu.hashx.perf_record import (
        RECORDED_STAGED_INGEST_BPS,
        STAGED_INGEST_DEGRADED_FRACTION,
    )

    try:
        import tempfile

        from benchmarks.host_ingest import bench_staged_ingest

        with tempfile.TemporaryDirectory() as _staged_tmp:
            rungs = bench_staged_ingest(
                raws, 1, [1], repeats=2, tmpdir=_staged_tmp
            )
        staged_bps = rungs[1]
        extra["staged_ingest_bps"] = round(staged_bps)
        extra["staged_ingest_vs_recorded"] = round(
            staged_bps / RECORDED_STAGED_INGEST_BPS, 2
        )
        if rungs[0] > 0:
            extra["staged_overhead_pct"] = round(
                (rungs[0] - staged_bps) / rungs[0] * 100.0, 1
            )
        if staged_bps < (
            STAGED_INGEST_DEGRADED_FRACTION * RECORDED_STAGED_INGEST_BPS
        ):
            extra["staged_ingest_degraded"] = True
    except (ImportError, NameError):
        pass  # bare package, or the ingest fixtures above didn't build

    # Telemetry plane (round 14): what the stage spans cost the same
    # ingest pipeline — blocks/s through the node's dispatch front door
    # with telemetry on vs off (benchmarks/telemetry_overhead.py), the
    # with-telemetry rate reported against the SAME recorded host-ingest
    # constant so a creeping observability tax shows up in the bench
    # JSON like any other regression.
    try:
        from benchmarks.telemetry_overhead import bench_quick as tel_quick

        to = tel_quick(blocks=300, repeats=3)
        extra["ingest_with_telemetry_bps"] = to["ingest_telemetry_bps"]
        extra["ingest_with_telemetry_vs_recorded"] = round(
            to["ingest_telemetry_bps"] / RECORDED_HOST_INGEST_BPS, 2
        )
        extra["telemetry_overhead_pct"] = to["overhead_pct"]
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Untrusted-path validation (round 8): quick same-session
    # revalidation measurement — serial vs batched signature lane on a
    # small store — reported against the ONE recorded constant
    # (perf_record.py RECORDED_REVALIDATE_BPS), the same
    # denominator-pinning convention as the ratios above.
    from p1_tpu.hashx.perf_record import (
        RECORDED_REVALIDATE_BPS,
        REVALIDATE_DEGRADED_FRACTION,
    )

    try:
        from benchmarks.sig_verify import bench_revalidate

        reval = bench_revalidate(400, repeats=3)
        extra["revalidate_bps"] = reval["revalidate_bps"]
        extra["revalidate_speedup"] = reval["revalidate_speedup"]
        extra["revalidate_vs_recorded"] = round(
            reval["revalidate_bps"] / RECORDED_REVALIDATE_BPS, 2
        )
        if (
            reval["revalidate_bps"]
            < REVALIDATE_DEGRADED_FRACTION * RECORDED_REVALIDATE_BPS
        ):
            extra["revalidate_degraded"] = True
        from p1_tpu.core import keys as _keys

        extra["sig_backend"] = _keys.backend()
    except ImportError:
        pass

    # Native + device Ed25519 (round 15): per-signature cost of the
    # native C++ batch engine against its recorded pin, and — only when
    # P1_BENCH_DEVICE is set, because every mesh shape pays a
    # multi-minute XLA compile on a small host — the device-sharded MSM
    # (benchmarks/sig_verify.py has the full per-backend + scaling
    # harness).  LOWER is better for both ratios.
    from p1_tpu.hashx.perf_record import (
        RECORDED_SIG_DEVICE_MS,
        RECORDED_SIG_NATIVE_MS,
        SIG_DEGRADED_FACTOR,
    )

    try:
        from p1_tpu.core import _ed25519_native

        if _ed25519_native.available():
            from benchmarks.sig_verify import _make_triples, _rate
            from p1_tpu.core.keys import Keypair

            kps = [Keypair.from_seed_text(f"bench-nat-{i}") for i in range(8)]
            tr = _make_triples(1024, kps)
            native_ms = 1e3 / _rate(
                lambda: _ed25519_native.verify_batch(tr), 1024
            )
            extra["sig_native_ms"] = round(native_ms, 4)
            extra["sig_native_vs_recorded"] = round(
                native_ms / RECORDED_SIG_NATIVE_MS, 2
            )
            if native_ms > SIG_DEGRADED_FACTOR * RECORDED_SIG_NATIVE_MS:
                extra["sig_native_degraded"] = True
        import os as _os

        if _os.environ.get("P1_BENCH_DEVICE"):
            from benchmarks.sig_verify import bench_device

            dv = bench_device(batch=256, device_counts=(8,), repeats=2)
            if dv.get("device_us_per_sig"):
                device_ms = dv["device_us_per_sig"] / 1e3
                extra["sig_device_ms"] = round(device_ms, 2)
                extra["sig_device_vs_recorded"] = round(
                    device_ms / RECORDED_SIG_DEVICE_MS, 2
                )
                if device_ms > SIG_DEGRADED_FACTOR * RECORDED_SIG_DEVICE_MS:
                    extra["sig_device_degraded"] = True
    except ImportError:
        pass  # bare install without the benchmarks/ tree

    # Query serving plane (round 9): quick same-session measurement of
    # cached proofs/s (benchmarks/query_plane.py), with the serial
    # baseline from the SAME run so the speedup is never a cross-session
    # artifact — reported against the ONE recorded constant
    # (perf_record.py RECORDED_QUERY_QPS), same convention as above.
    from p1_tpu.hashx.perf_record import (
        QUERY_DEGRADED_FRACTION,
        RECORDED_QUERY_QPS,
    )

    try:
        from benchmarks.query_plane import bench_quick

        qp = bench_quick(repeats=3)
        extra["query_qps"] = qp["proof_cached_qps"]
        extra["query_serial_qps"] = qp["proof_serial_qps"]
        extra["query_batched_qps"] = qp["proof_batched_qps"]
        extra["query_vs_recorded"] = round(
            qp["proof_cached_qps"] / RECORDED_QUERY_QPS, 2
        )
        if qp["proof_cached_qps"] < QUERY_DEGRADED_FRACTION * RECORDED_QUERY_QPS:
            extra["query_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Wallet push plane (round 21): live subscriptions held and p95
    # per-block notify latency on the shared-decode push path
    # (benchmarks/wallet_plane.py bench_quick, 20k sessions — the 100k
    # acceptance run is the benchmark's main()).  LOWER is better for
    # the p95, so notify_vs_recorded > 1 means slower than the record
    # (perf_record.py RECORDED_NOTIFY_P95_MS).
    from p1_tpu.hashx.perf_record import (
        NOTIFY_DEGRADED_FACTOR,
        RECORDED_NOTIFY_P95_MS,
        RECORDED_WALLET_SUBS,
    )

    try:
        from benchmarks.wallet_plane import bench_quick as wallet_quick

        wp = wallet_quick()
        extra["wallet_subs"] = wp["wallet_subs"]
        extra["notify_p95_ms"] = wp["notify_p95_ms"]
        extra["notify_events_per_sec"] = wp["notify_events_per_sec"]
        extra["notify_vs_recorded"] = round(
            wp["notify_p95_ms"] / RECORDED_NOTIFY_P95_MS, 2
        )
        if wp["wallet_subs"] < RECORDED_WALLET_SUBS or (
            wp["notify_p95_ms"]
            > NOTIFY_DEGRADED_FACTOR * RECORDED_NOTIFY_P95_MS
        ):
            extra["notify_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Fleet provisioning (round 22): snapshot cold-start seconds for a
    # `p1 serve --bootstrap` replica and the kill-one-replica notify
    # p95 (benchmarks/wallet_plane.py bench_fleet_quick — 3 replicas x
    # 24 spread sessions, most-loaded replica killed mid-push).  LOWER
    # is better for both; fleet_missed must be 0 regardless of load
    # (a missed confirmation is a bug, not a regression).
    from p1_tpu.hashx.perf_record import (
        FLEET_DEGRADED_FACTOR,
        RECORDED_FLEET_COLD_START_S,
        RECORDED_FLEET_NOTIFY_P95_MS,
    )

    try:
        from benchmarks.wallet_plane import bench_fleet_quick

        fp = bench_fleet_quick()
        extra["fleet_cold_start_s"] = fp["fleet_cold_start_s"]
        extra["fleet_notify_p95_ms"] = fp["fleet_notify_p95_ms"]
        extra["fleet_failovers"] = fp["fleet_failovers"]
        extra["fleet_missed"] = fp["fleet_missed"]
        extra["fleet_cold_start_vs_recorded"] = round(
            fp["fleet_cold_start_s"] / RECORDED_FLEET_COLD_START_S, 2
        )
        extra["fleet_notify_vs_recorded"] = round(
            fp["fleet_notify_p95_ms"] / RECORDED_FLEET_NOTIFY_P95_MS, 2
        )
        if (
            fp["fleet_missed"] > 0
            or fp["fleet_cold_start_s"]
            > FLEET_DEGRADED_FACTOR * RECORDED_FLEET_COLD_START_S
            or fp["fleet_notify_p95_ms"]
            > FLEET_DEGRADED_FACTOR * RECORDED_FLEET_NOTIFY_P95_MS
        ):
            extra["fleet_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Deterministic network simulator (round 10): node-seconds of
    # simulated mesh per wall second on a quick 100-node partition-heal
    # (benchmarks/netsim_scale.py scales linearly enough that the small
    # run tracks the pinned 200-node figure within the guard band) —
    # reported against the ONE recorded constant (perf_record.py
    # RECORDED_SIM_RATE), same convention as above.
    from p1_tpu.hashx.perf_record import (
        RECORDED_SIM_RATE,
        SIM_DEGRADED_FRACTION,
    )

    try:
        from benchmarks.netsim_scale import bench_sim

        sim = bench_sim(nodes=100, seed=0)
        extra["sim_nodes_per_sec"] = sim["sim_nodes_per_sec"]
        extra["sim_events_per_sec"] = sim["events_per_wall_s"]
        extra["sim_ok"] = sim["ok"]
        extra["sim_vs_recorded"] = round(
            sim["sim_nodes_per_sec"] / RECORDED_SIM_RATE, 2
        )
        if sim["sim_nodes_per_sec"] < SIM_DEGRADED_FRACTION * RECORDED_SIM_RATE:
            extra["sim_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Sharded far field (round 17): node-seconds per wall second on a
    # quick 2,000-node far-field run at 2 process shards
    # (benchmarks/netsim_scale.py bench_far_field; the full 10k ladder
    # is the --far table).  Header-only node-seconds — read the figure
    # against RECORDED_SIM_SHARDED_RATE, never against the full-node
    # sim rate above (docs/PERF.md spells out what the far-field model
    # omits).
    from p1_tpu.hashx.perf_record import (
        RECORDED_SIM_SHARDED_RATE,
        SIM_SHARDED_DEGRADED_FRACTION,
    )

    try:
        from benchmarks.netsim_scale import bench_far_field

        far = bench_far_field(nodes=2000, shards=2, seed=0)
        extra["sim_sharded_nodes_per_sec"] = far["sim_sharded_nodes_per_sec"]
        extra["sim_sharded_ok"] = far["ok"]
        extra["sim_sharded_vs_recorded"] = round(
            far["sim_sharded_nodes_per_sec"] / RECORDED_SIM_SHARDED_RATE, 2
        )
        if (
            far["sim_sharded_nodes_per_sec"]
            < SIM_SHARDED_DEGRADED_FRACTION * RECORDED_SIM_SHARDED_RATE
        ):
            extra["sim_sharded_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Relay bandwidth budget (round 23): tx-plane bytes per delivered
    # tx and submit-to-everywhere p95 on the reconciliation arm of a
    # quick flood-vs-recon A/B over shaped 64 kbps uplinks
    # (benchmarks/netsim_scale.py bench_relay; the 16-node acceptance
    # run is `p1 sim relay-budget`).  Both figures are virtual-time
    # deterministic — drift past the band is a protocol regression
    # (duplicate serves, capacity under-estimates, demotion floods),
    # not host noise.  LOWER is better for both.
    from p1_tpu.hashx.perf_record import (
        RECORDED_RELAY_BYTES_PER_TX,
        RECORDED_TX_PROP_P95_MS,
        RELAY_DEGRADED_FACTOR,
    )

    try:
        from benchmarks.netsim_scale import bench_relay

        rl = bench_relay(
            nodes=10,
            senders=4,
            txs_per_sender=24,
            storm_vs=10.0,
            min_reduction=3.0,
        )
        extra["relay_bytes_per_tx"] = rl["relay_bytes_per_tx"]
        extra["tx_prop_p95_ms"] = rl["tx_prop_p95_ms"]
        extra["relay_reduction"] = rl["reduction"]
        extra["relay_ok"] = rl["ok"]
        extra["relay_bytes_vs_recorded"] = round(
            rl["relay_bytes_per_tx"] / RECORDED_RELAY_BYTES_PER_TX, 2
        )
        extra["tx_prop_vs_recorded"] = round(
            rl["tx_prop_p95_ms"] / RECORDED_TX_PROP_P95_MS, 2
        )
        if (
            rl["relay_bytes_per_tx"]
            > RELAY_DEGRADED_FACTOR * RECORDED_RELAY_BYTES_PER_TX
            or rl["tx_prop_p95_ms"]
            > RELAY_DEGRADED_FACTOR * RECORDED_TX_PROP_P95_MS
        ):
            extra["relay_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Chaos plane (round 11): combined-fault schedules per wall second
    # (benchmarks/chaos_rate.py) against the ONE recorded constant
    # (perf_record.py RECORDED_CHAOS_RATE), same convention as above.
    # A quick 5-schedule probe: it tracks the pinned 10-schedule figure
    # within the guard band at half the bench cost.
    from p1_tpu.hashx.perf_record import (
        CHAOS_DEGRADED_FRACTION,
        RECORDED_CHAOS_RATE,
    )

    try:
        from benchmarks.chaos_rate import bench_chaos

        ch = bench_chaos(schedules=5)
        extra["chaos_schedules_per_sec"] = ch["chaos_schedules_per_sec"]
        extra["chaos_virtual_per_wall"] = ch["virtual_per_wall"]
        extra["chaos_ok"] = ch["ok"]
        extra["chaos_vs_recorded"] = round(
            ch["chaos_schedules_per_sec"] / RECORDED_CHAOS_RATE, 2
        )
        if (
            ch["chaos_schedules_per_sec"]
            < CHAOS_DEGRADED_FRACTION * RECORDED_CHAOS_RATE
        ):
            extra["chaos_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Untrusted snapshot sync (round 12): seconds from a cold snapshot
    # file to serving queries (benchmarks/snapshot_boot.py), with the
    # batched-revalidation baseline from the SAME run — reported
    # against the ONE recorded constant (perf_record.py
    # RECORDED_SNAPSHOT_BOOT_S; LOWER is better, so vs_recorded > 1
    # means slower than the record).
    from p1_tpu.hashx.perf_record import (
        RECORDED_SNAPSHOT_BOOT_S,
        SNAPSHOT_DEGRADED_FACTOR,
    )

    try:
        from benchmarks.snapshot_boot import bench_quick as snap_quick

        sb = snap_quick(blocks=800, repeats=3)
        extra["snapshot_boot_s"] = sb["snapshot_boot_s"]
        extra["snapshot_revalidate_s"] = sb["revalidate_boot_s"]
        extra["snapshot_vs_recorded"] = round(
            sb["snapshot_boot_s"] / RECORDED_SNAPSHOT_BOOT_S, 2
        )
        if sb["snapshot_boot_s"] > SNAPSHOT_DEGRADED_FACTOR * (
            RECORDED_SNAPSHOT_BOOT_S
        ):
            extra["snapshot_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Archive scale (round 18, chain/segstore.py + headerplane.py):
    # the synthetic segmented-archive probe — whole-archive
    # packed-header resume rate and the boot-to-serving peak RSS
    # (benchmarks/archive_scale.py).  The default probe is the 100k
    # shape (seconds); ``P1_BENCH_ARCHIVE=1`` runs the full 10M
    # acceptance shape instead (minutes of build + a ~3 GB scratch
    # store) — the slow ladder docs/PERF.md "Archive scale" records.
    from p1_tpu.hashx.perf_record import (
        ARCHIVE_BOOT_RSS_DEGRADED_FACTOR,
        ARCHIVE_RESUME_DEGRADED_FRACTION,
        RECORDED_ARCHIVE_BOOT_RSS_MB,
        RECORDED_ARCHIVE_RESUME_BPS,
    )

    try:
        from benchmarks.archive_scale import bench_quick as arch_quick

        ar = arch_quick(
            blocks=10_000_000
            if os.environ.get("P1_BENCH_ARCHIVE")
            else 100_000
        )
        extra["archive_blocks"] = ar["blocks"]
        extra["archive_resume_bps"] = ar["archive_resume_bps"]
        extra["archive_boot_s"] = ar["archive_boot_s"]
        extra["archive_boot_rss_mb"] = ar["archive_boot_rss_mb"]
        extra["archive_query_qps"] = ar["archive_query_qps"]
        extra["archive_resume_vs_recorded"] = round(
            ar["archive_resume_bps"] / RECORDED_ARCHIVE_RESUME_BPS, 2
        )
        extra["archive_rss_vs_recorded"] = round(
            ar["archive_boot_rss_mb"] / RECORDED_ARCHIVE_BOOT_RSS_MB, 2
        )
        if (
            ar["archive_resume_bps"]
            < ARCHIVE_RESUME_DEGRADED_FRACTION * RECORDED_ARCHIVE_RESUME_BPS
            or ar["archive_boot_rss_mb"]
            > ARCHIVE_BOOT_RSS_DEGRADED_FACTOR * RECORDED_ARCHIVE_BOOT_RSS_MB
        ):
            extra["archive_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Always-on maintenance plane (round 20, benchmarks/
    # maintenance_cadence.py): incremental snapshot rebuilds/sec (the
    # continuous-publication cadence headroom, with the same-session
    # full-rebuild speedup alongside) and the in-RAM live-rebase
    # latency, against the pinned records (perf_record.py
    # RECORDED_SNAPSHOT_CADENCE_BPS / RECORDED_REBASE_MS; the rebase
    # figure is lower-is-better, so vs_recorded > 1 means slower).
    from p1_tpu.hashx.perf_record import (
        REBASE_DEGRADED_FACTOR,
        RECORDED_REBASE_MS,
        RECORDED_SNAPSHOT_CADENCE_BPS,
        SNAPSHOT_CADENCE_DEGRADED_FRACTION,
    )

    try:
        from benchmarks.maintenance_cadence import (
            bench_quick as cadence_quick,
        )

        mc = cadence_quick()
        extra["snapshot_incr_builds_per_sec"] = mc[
            "snapshot_incr_builds_per_sec"
        ]
        extra["snapshot_cadence_speedup"] = mc["snapshot_cadence_speedup"]
        extra["rebase_ms"] = mc["rebase_ms"]
        extra["snapshot_cadence_vs_recorded"] = round(
            mc["snapshot_incr_builds_per_sec"]
            / RECORDED_SNAPSHOT_CADENCE_BPS,
            2,
        )
        extra["rebase_vs_recorded"] = round(
            mc["rebase_ms"] / RECORDED_REBASE_MS, 2
        )
        if (
            mc["snapshot_incr_builds_per_sec"]
            < SNAPSHOT_CADENCE_DEGRADED_FRACTION
            * RECORDED_SNAPSHOT_CADENCE_BPS
            or mc["rebase_ms"] > REBASE_DEGRADED_FACTOR * RECORDED_REBASE_MS
        ):
            extra["maintenance_degraded"] = True
    except ImportError:
        pass  # installed as a bare package without the benchmarks/ tree

    # Static analysis plane (round 13, p1_tpu/analysis): unsettled
    # finding count (unallowlisted + stale grants — tier-1 holds it at
    # zero, so ANY nonzero here is drift the round record must show)
    # and the whole-package pass's wall time (the acceptance budget is
    # ~5 s on this 1-vCPU host; creeping past it would push `p1 lint`
    # out of the edit loop).
    try:
        from p1_tpu.analysis import run_analysis

        t0 = time.perf_counter()
        lint = run_analysis()
        extra["lint_wall_s"] = round(time.perf_counter() - t0, 3)
        extra["lint_findings"] = (
            len(lint.violations) + len(lint.stale) + len(lint.parse_errors)
        )
        extra["lint_granted"] = len(lint.granted)
        extra["lint_rules"] = len(lint.rules)
        # Round 16: the interprocedural layer's size — node/edge growth
        # is the leading indicator of wall-time creep (the fixed point
        # and the per-node summaries are both linear in these).
        extra["lint_callgraph_nodes"] = lint.callgraph_nodes
        extra["lint_callgraph_edges"] = lint.callgraph_edges
    except ImportError:
        pass  # installed as a bare package without the analysis tree

    from p1_tpu.hashx.perf_record import RECORDED_CPU_BASELINE_HPS

    print(
        json.dumps(
            {
                "metric": "sha256d_hashes_per_sec_per_chip",
                "value": round(device_hps),
                "unit": "H/s",
                # Two ratios, one kernel (VERDICT r5 weak #2): the live
                # same-session denominator moves with host load (up to
                # ~3.6x across rounds), so round-over-round comparisons
                # use vs_recorded — the pinned healthy CPU rate in
                # hashx/perf_record.py — while vs_baseline stays the
                # honest same-box-same-moment measurement.  docs/PERF.md
                # "Which ratio to trust" spells out when each applies.
                "vs_baseline": round(device_hps / cpu_hps, 1),
                "vs_recorded": round(
                    device_hps / RECORDED_CPU_BASELINE_HPS, 1
                ),
                "recorded_cpu_baseline_hps": round(
                    RECORDED_CPU_BASELINE_HPS
                ),
                "platform": platform,
                "backend": device.name,
                "cpu_baseline_hps": round(cpu_hps),
                "load_avg_1m": load_1m,
                "load_avg_5m": load_5m,
                "cpu_count": os.cpu_count(),
                "peak_rss_bytes": peak_rss_bytes,
                "time_to_block_d20_s": round(ttb, 3),
                "batch": device.batch,
                **extra,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
