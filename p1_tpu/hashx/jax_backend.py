"""JAX hash backend: the XLA-compiled nonce search (CPU or TPU).

Capability parity: the ``JaxTPUBackend`` registry entry of the north star
(BASELINE.json:5), in its pure-XLA form — the Pallas-kernel variant is the
``tpu`` backend (pallas_backend.py) and the multi-chip variant is the
``sharded`` backend (sharded.py), both of which reuse this module's
pipelined host loop.  ``search`` runs a host loop of jitted device steps
with **async double-buffering**: step k+1 is dispatched before step k's
4-byte result is read back, so the device never idles on the host (JAX's
async dispatch gives this for free as long as we delay ``int()``-ing a
result until the next step is enqueued).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from p1_tpu.core.header import target_from_difficulty, target_to_words
from p1_tpu.hashx.backend import HashBackend, SearchResult, register
from p1_tpu.hashx.jax_sha256 import jit_search_step
from p1_tpu.hashx.sha256_ref import header_midstate, header_tail_words, sha256d

_U32 = jnp.uint32

#: A step function: (midstate, tail, target, nonce_base) -> uint32 offset of
#: the earliest hit in [nonce_base, nonce_base + step_span), or step_span.
StepFn = Callable[..., jax.Array]

#: Default device-step batch by platform.  The TPU sweep peaked near 2**24
#: per step (106 MH/s pipelined; 2**26 gains little and slows aborts); on
#: CPU the fori_loop carry holds ~25 live uint32 arrays per lane, so 2**24
#: would mean a ~2 GB working set and minutes between abort checks on a
#: 1-vCPU box — 2**18 keeps both in the tens-of-MB / sub-second range.
_PLATFORM_BATCH = {"cpu": 1 << 18, "tpu": 1 << 24}


def default_batch(platform: str | None = None) -> int:
    p = platform or jax.default_backend()
    return _PLATFORM_BATCH.get(p, 1 << 20)


def is_tpu_platform(platform: str | None = None) -> bool:
    """True when ``platform`` (default: JAX's default backend) is a TPU."""
    return (platform or jax.default_backend()) == "tpu"


#: Opening-ramp parameters (see ``PipelinedSearchMixin.search``).  The floor
#: is sized so a difficulty-20 hit (expected at ~2²⁰ nonces) lands in the
#: first step with ~98% probability.  Whether a lower floor pays on a
#: directly attached chip, where a dispatch costs far less than the
#: remote-device runtime it was sized on, is an open question (PERF.md).
_RAMP_FLOOR = 1 << 22
_RAMP_FACTOR = 8
#: Ramp only when a hit inside the floor span is plausible: at difficulty d
#: the expected hit is at 2^d nonces, so for d > 26 the opening steps almost
#: never hit and would only add dispatch latency to a long scan.
_RAMP_MAX_DIFFICULTY = 26


class PipelinedSearchMixin:
    """The host loop shared by every device-stepped backend.

    Subclasses provide ``step_span`` (nonces evaluated per full device step)
    and ``_make_step(span)`` (a jitted step function for a given span).
    ``search`` then scans an arbitrary range with a one-step pipeline and
    host-side masking of the partial final step.

    **Adaptive opening ramp**: a fresh scan (nonce_start == 0) at a
    difficulty where an early hit is plausible starts with a small step
    (``ramp_floor``) and grows geometrically to ``step_span``, so
    time-to-block is one dispatch latency instead of a full-batch step —
    at difficulty 20 a 2²⁷-batch backend would otherwise spend ~10× the
    expected search time on granularity alone.  Throughput scans
    (high difficulty, or resumed ranges) skip the ramp entirely.
    """

    step_span: int
    #: Smallest opening step; None disables the ramp (sharded backend: the
    #: per-device batch is baked into the mesh program).
    ramp_floor: int | None = _RAMP_FLOOR

    def _make_step(self, span: int) -> StepFn:
        raise NotImplementedError

    def sha256d(self, data: bytes) -> bytes:
        return sha256d(data)  # single digests stay on host

    def _search_arrays(self, header_prefix: bytes, difficulty: int):
        midstate = jnp.array(header_midstate(header_prefix), dtype=_U32)
        tail = jnp.array(header_tail_words(header_prefix), dtype=_U32)
        target = jnp.array(
            target_to_words(target_from_difficulty(difficulty)), dtype=_U32
        )
        return midstate, tail, target

    def search(
        self, header_prefix: bytes, nonce_start: int, count: int, difficulty: int
    ) -> SearchResult:
        self._check_search_args(header_prefix, nonce_start, count, difficulty)
        midstate, tail, target = self._search_arrays(header_prefix, difficulty)

        ramping = (
            self.ramp_floor is not None
            and nonce_start == 0
            and difficulty <= _RAMP_MAX_DIFFICULTY
            and self.step_span > self.ramp_floor
        )
        span = self.ramp_floor if ramping else self.step_span

        # Batched scan with a one-step pipeline.  Each step covers
        # [base, base+span); a partial final step is masked on the host
        # by re-checking the hit offset against the remaining count.
        pending: list[tuple[int, int, object]] = []  # (base, valid, device idx)
        done = 0
        result: SearchResult | None = None
        while done < count and result is None:
            base = nonce_start + done
            valid = min(span, count - done)
            idx = self._make_step(span)(midstate, tail, target, _U32(base))
            pending.append((base, valid, idx))
            done += valid
            span = min(span * _RAMP_FACTOR, self.step_span)
            if len(pending) > 1:
                result = self._drain_one(pending, nonce_start)
        while result is None and pending:
            result = self._drain_one(pending, nonce_start)
        if result is not None:
            return result
        return SearchResult(None, count)

    def _drain_one(self, pending: list, nonce_start: int) -> SearchResult | None:
        base, valid, idx = pending.pop(0)
        offset = int(np.asarray(idx))  # blocks until this step is done
        if offset < valid:
            nonce = base + offset
            return SearchResult(nonce, nonce - nonce_start + 1)
        return None


@register("jax")
class JaxBackend(PipelinedSearchMixin, HashBackend):
    """XLA-compiled SHA-256d search on a single JAX device."""

    def __init__(self, batch: int | None = None, platform: str | None = None):
        if batch is None:
            batch = default_batch(platform)
        if batch <= 0 or batch & (batch - 1):
            raise ValueError(f"batch must be a power of two, got {batch}")
        self.batch = batch
        self.step_span = batch
        self.platform = platform

    def _make_step(self, span: int) -> StepFn:
        return jit_search_step(span, self.platform)
