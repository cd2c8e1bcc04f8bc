"""``Miner.search_nonce()``: the proof-of-work search loop.

Capability parity: the reference miner's inner loop — "double-SHA-256 over a
serialized ``BlockHeader`` with an incrementing nonce" (BASELINE.json:5) —
restructured for a device-stepped world: the miner asks its ``HashBackend``
to scan a *chunk* of nonce space per call (millions of candidates for the
JAX/TPU backends, which internally pipeline jitted device steps), checks the
abort signal between chunks so a new chain tip cancels stale work promptly,
and rolls the header timestamp to reopen the nonce space when all 2**32
candidates are exhausted (the classic extra-nonce trick, without touching
the merkle root).
"""

from __future__ import annotations

import dataclasses
import threading
import time

from p1_tpu.core.header import BlockHeader
from p1_tpu.hashx.backend import HashBackend, StepCounts, get_backend

NONCE_SPACE = 1 << 32


@dataclasses.dataclass
class MineStats(StepCounts):
    """Counters from one ``search_nonce`` call (metrics surface): the
    device-step counters of its backend calls (``StepCounts``; all 0 on a
    backend with no device steps) and the call's own."""

    hashes_done: int = 0
    elapsed_s: float = 0.0
    timestamp_rolls: int = 0
    aborted: bool = False

    @property
    def hashes_per_sec(self) -> float:
        return self.hashes_done / self.elapsed_s if self.elapsed_s > 0 else 0.0


class Miner:
    """Drives a ``HashBackend`` over nonce space to seal block headers.

    ``chunk`` is the number of nonces requested per backend call — the abort
    granularity.  The JAX backends pipeline device steps *within* a chunk, so
    the chunk should span several device batches; ``chunk=None`` derives
    4x the backend's ``step_span`` (the nonces one device step covers —
    mesh-wide for the sharded backend) when it has one, keeping the
    pipeline full, else a CPU-friendly 2**22.
    """

    def __init__(
        self,
        backend: str | HashBackend = "cpu",
        chunk: int | None = None,
        max_timestamp_rolls: int | None = None,
    ):
        self.backend = get_backend(backend) if isinstance(backend, str) else backend
        if chunk is None:
            span = getattr(self.backend, "step_span", None)
            chunk = 4 * span if span else 1 << 22
        if chunk <= 0:
            raise ValueError("chunk must be positive")
        self.chunk = chunk
        self.max_timestamp_rolls = max_timestamp_rolls
        self.last_stats = MineStats()

    def search_nonce(
        self,
        header: BlockHeader,
        abort: threading.Event | None = None,
        start_nonce: int = 0,
    ) -> BlockHeader | None:
        """Find a sealed header whose hash meets ``header.difficulty``.

        Returns the input header with the winning nonce (and possibly a
        rolled timestamp) attached, or None if ``abort`` was set first.
        The search is deterministic for a given header: nonce space is
        scanned in increasing order from ``start_nonce``, so the earliest
        valid nonce at the original timestamp is always preferred.
        """
        stats = MineStats()
        self.last_stats = stats
        t0 = time.perf_counter()
        try:
            while True:
                prefix = header.mining_prefix()
                nonce = start_nonce
                while nonce < NONCE_SPACE:
                    if self._chunk_sync(abort):
                        stats.aborted = True
                        return None
                    count = min(self.chunk, NONCE_SPACE - nonce)
                    res = self.backend.search(
                        prefix, nonce, count, header.difficulty
                    )
                    stats.hashes_done += res.hashes_done
                    if res.steps is not None:
                        stats.add(res.steps)
                    if res.nonce is not None:
                        return header.with_nonce(res.nonce)
                    nonce += count
                # Nonce space exhausted: roll the timestamp and rescan.
                if (
                    self.max_timestamp_rolls is not None
                    and stats.timestamp_rolls >= self.max_timestamp_rolls
                ):
                    return None
                stats.timestamp_rolls += 1
                header = header.with_timestamp(header.timestamp + 1)
                start_nonce = 0
        finally:
            stats.elapsed_s = time.perf_counter() - t0

    def _chunk_sync(self, abort: threading.Event | None) -> bool:
        """Per-chunk stop decision, called before every backend call.

        Hook point for lockstep mining: the default is a local abort-event
        check; the multi-host PodMiner (p1_tpu/parallel/pod.py) overrides
        it to broadcast the leader's decision so every process leaves the
        chunk loop at the same iteration.
        """
        return abort is not None and abort.is_set()
