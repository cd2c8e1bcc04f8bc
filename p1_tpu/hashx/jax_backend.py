"""JAX hash backend: the XLA-compiled nonce search (CPU or TPU).

Capability parity: the ``JaxTPUBackend`` registry entry of the north star
(BASELINE.json:5), in its pure-XLA form — the Pallas-kernel variant is the
``tpu`` backend (pallas_backend.py) and the multi-chip variant is the
``sharded`` backend (sharded.py), both of which reuse this module's
pipelined host loop.  ``search`` runs a host loop of jitted device steps
with **async double-buffering**: step k+1 is dispatched before step k's
8-byte result is read back, so the device never idles on the host (JAX's
async dispatch gives this for free as long as we delay ``int()``-ing a
result until the next step is enqueued).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import time
from typing import Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from p1_tpu.core.header import target_from_difficulty, target_to_words
from p1_tpu.hashx.backend import (
    HashBackend,
    SearchResult,
    StepCounts,
    register,
    step_spans_on,
)
from p1_tpu.hashx.jax_sha256 import jit_search_step
from p1_tpu.hashx.sha256_ref import header_midstate, header_tail_words, sha256d

_U32 = jnp.uint32

#: A step function: (midstate, tail, target, nonce_base) -> uint32 offset of
#: the earliest hit in [nonce_base, nonce_base + step_span), or step_span.
#: Chained, (midstate, tail, target, nonce_base, prior) -> result: the
#: step's uint32 (offset, found) in one device array, ``found`` 1 when the
#: step hit or skipped; ``prior`` is the result of the launch before, and a
#: step whose prior's ``found`` is set skips its work.
StepFn = Callable[..., jax.Array]

#: Default device-step batch by platform.  On TPU, 2**24 was the peak of a
#: sweep through the removed remote-device runtime; no chip cell runs the
#: XLA backend on TPU, so it is not measured on the chip (ROADMAP D5).  On
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
#: first step with ~98% probability.  It was sized on the removed
#: remote-device runtime, where a dispatch cost far more; whether a lower
#: floor pays on a directly attached chip is not measured yet (ROADMAP
#: S2 lever 5).
_RAMP_FLOOR = 1 << 22
_RAMP_FACTOR = 8
#: Ramp only when a hit inside the floor span is plausible: at difficulty d
#: the expected hit is at 2^d nonces, so for d > 26 the opening steps almost
#: never hit and would only add dispatch latency to a long scan.
_RAMP_MAX_DIFFICULTY = 26


class _Launch(NamedTuple):
    """One dispatched device step."""

    id: int
    base: int  # first nonce
    valid: int  # nonces of the step inside the requested range
    span: int  # nonces the device evaluates
    result: jax.Array  # the step's (offset, found), on the device

    def prior(self) -> jax.Array:
        """The result the next launch is chained to.  Its flag is set
        only when this launch hit below ``span``, which the host reads as
        a hit only when the launch is full: a partial launch's hit beyond
        ``valid`` is a miss.  A partial launch is the call's last, so
        nothing is chained to one."""
        assert self.valid == self.span, "a launch chained to a partial launch"
        return self.result


class _NoSpan(contextlib.nullcontext):
    """``TraceAnnotation``'s stand-in while step spans are off, down to
    ``set_metadata``.  (With no profiler trace recording,
    ``TraceAnnotation`` itself is a native no-op, cheaper than this.)"""

    def __enter__(self):
        return self

    def set_metadata(self, **metadata) -> None:
        pass


_NO_SPAN = _NoSpan()


def _no_span(name: str, **metadata):
    return _NO_SPAN


def step_span_maker():
    """How this thread's device steps open their ``p1.*`` spans:
    ``TraceAnnotation`` while step spans are on (``backend.step_spans``),
    else a stand-in that writes nothing."""
    return TraceAnnotation if step_spans_on() else _no_span


class PipelinedSearchMixin:
    """The host loop shared by every device-stepped backend.

    Subclasses provide ``step_span`` (nonces evaluated per full device step;
    the largest, where ``_step_span_for`` sizes it from the difficulty)
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

    **Chained steps**: each launch takes the result of the launch before
    it as an operand and skips its work on the device when that launch
    hit, so the step in flight behind a hit costs little of the device's
    time.  Offset and found flag share one array, so a launch adds one
    buffer to the unchained step's, not two: the flag never leaves the
    device, and the host neither waits for it nor uploads one per launch.

    **Spans and counters**: each call returns its ``StepCounts`` with the
    result, and writes profiler spans on the executor thread, on the
    device ops' clock: ``p1.search.prepare`` (midstate and upload),
    ``p1.search.dispatch`` (one per launch: ``launch`` id, ``nonces``
    span) and ``p1.search.readback`` (the blocking read, ``launch`` id).
    A launch with no readback span was abandoned.
    """

    step_span: int
    #: Smallest opening step; None disables the ramp (sharded backend: it
    #: sizes its mesh step from the difficulty instead).
    ramp_floor: int | None = _RAMP_FLOOR
    #: The JAX platform the steps run on; None for the default backend.
    platform: str | None = None

    def _make_step(self, span: int) -> StepFn:
        """A jitted step of ``span`` nonces, which takes the chained form."""
        raise NotImplementedError

    def _step_span_for(self, difficulty: int) -> int:
        """The full step of a search at ``difficulty``: ``step_span``,
        unless the backend sizes its step from the difficulty (the
        sharded backend does)."""
        return self.step_span

    def _placement(self):
        """Where the steps run: a device or sharding for ``device_put``,
        None for the default device."""
        return jax.devices(self.platform)[0] if self.platform else None

    @functools.cached_property
    def _no_prior(self) -> jax.Array:
        """The prior result of a call's first launch, "no hit before it":
        made once, and kept on the device."""
        return jax.device_put(np.zeros(2, np.uint32), self._placement())

    def sha256d(self, data: bytes) -> bytes:
        return sha256d(data)  # single digests stay on host

    def _search_arrays(self, header_prefix: bytes, difficulty: int):
        midstate = jnp.array(header_midstate(header_prefix), dtype=_U32)
        tail = jnp.array(header_tail_words(header_prefix), dtype=_U32)
        target = jnp.array(
            target_to_words(target_from_difficulty(difficulty)), dtype=_U32
        )
        return midstate, tail, target

    @functools.cached_property
    def _launch_ids(self) -> Iterator[int]:
        """This backend's launch ids: 0, 1, 2, ... in dispatch order."""
        return itertools.count()

    def search(
        self, header_prefix: bytes, nonce_start: int, count: int, difficulty: int
    ) -> SearchResult:
        self._check_search_args(header_prefix, nonce_start, count, difficulty)
        span_of = step_span_maker()
        steps = StepCounts()
        t = time.perf_counter()
        with span_of("p1.search.prepare"):
            midstate, tail, target = self._search_arrays(header_prefix, difficulty)
        steps.prepare_s = time.perf_counter() - t

        full = self._step_span_for(difficulty)
        ramping = (
            self.ramp_floor is not None
            and nonce_start == 0
            and difficulty <= _RAMP_MAX_DIFFICULTY
            and full > self.ramp_floor
        )
        span = self.ramp_floor if ramping else full

        # Batched scan with a one-step pipeline.  Each step covers
        # [base, base+span); a partial final step is masked on the host
        # by re-checking the hit offset against the remaining count.
        pending: list[_Launch] = []
        prior = self._no_prior
        done = 0
        hit: int | None = None
        while done < count and hit is None:
            base = nonce_start + done
            valid = min(span, count - done)
            launch = next(self._launch_ids)
            t = time.perf_counter()
            with span_of("p1.search.dispatch", launch=launch, nonces=span):
                result = self._make_step(span)(
                    midstate, tail, target, _U32(base), prior
                )
            steps.dispatch_s += time.perf_counter() - t
            steps.launches += 1
            steps.nonces_launched += span
            pending.append(_Launch(launch, base, valid, span, result))
            done += valid
            if done < count:
                prior = pending[-1].prior()
            span = min(span * _RAMP_FACTOR, full)
            if len(pending) > 1:
                hit = self._drain_one(pending, steps, span_of)
        while hit is None and pending:
            hit = self._drain_one(pending, steps, span_of)
        # What is still pending was dispatched and is never read back: the
        # step in flight behind a hit.  Each such launch is chained to the
        # hit, or to a launch chained to it, and so skipped its work.
        for p in pending:
            steps.launches_abandoned += 1
            steps.nonces_abandoned += p.span
            steps.launches_skipped += 1
            steps.nonces_skipped += p.span
        if hit is None:
            return SearchResult(None, count, steps)
        return SearchResult(hit, hit - nonce_start + 1, steps)

    def _drain_one(self, pending: list, steps: StepCounts, span_of) -> int | None:
        """Read back the oldest pending step: its hit's nonce, or None."""
        p = pending.pop(0)
        t = time.perf_counter()
        with span_of("p1.search.readback", launch=p.id):
            offset = int(np.asarray(p.result)[0])  # blocks until this step is done
        steps.readback_s += time.perf_counter() - t
        return p.base + offset if offset < p.valid else None


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
