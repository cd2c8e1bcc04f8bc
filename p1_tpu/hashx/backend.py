"""``HashBackend`` ABC and plugin registry.

Capability parity: the reference selects hash implementations through a
``HashBackend`` plugin registry keyed by name, so that adding a TPU backend
touches nothing outside ``miner/`` and ``hash/`` (BASELINE.json:5 — "The
existing ``HashBackend`` plugin registry gains a ``JaxTPUBackend`` entry").
Here the registry is the framework's own design: ``@register`` decorator,
``get_backend(name)`` factory, plus a lazy table for backends whose imports
are heavy (JAX) or optional (native .so), so ``import p1_tpu`` stays cheap.

The two operations every backend provides:

- ``sha256d(data)`` — one double-SHA-256 (validation path).
- ``search(prefix, nonce_start, count, difficulty)`` — scan candidate nonces
  ``[nonce_start, nonce_start+count)`` over a 76-byte header prefix and
  return the **earliest** nonce whose SHA-256d meets the difficulty target,
  or None.  This is the miner's hot loop (BASELINE.json:5).
"""

from __future__ import annotations

import abc
import contextlib
import dataclasses
import threading
from typing import Callable, Iterator

from p1_tpu.core.header import NONCE_OFFSET


@dataclasses.dataclass
class StepCounts:
    """Device-step counters of one or more ``search`` calls.

    Filled by the device-stepped backends (``PipelinedSearchMixin``); the
    host backends (cpu, native, numpy) have no steps and report none.  A
    launch is *abandoned* when it is dispatched and never read back: the
    step still in flight when ``search`` returns a hit.  It is *skipped*
    when it was chained to the launch that hit, and so did no hashing on
    the device.
    """

    launches: int = 0
    nonces_launched: int = 0
    launches_abandoned: int = 0
    nonces_abandoned: int = 0
    launches_skipped: int = 0
    nonces_skipped: int = 0
    prepare_s: float = 0.0  # midstate and upload of the input arrays
    dispatch_s: float = 0.0  # step launches, summed
    readback_s: float = 0.0  # blocking reads of step results, summed

    def add(self, other: "StepCounts") -> None:
        for name in _STEP_FIELDS:
            setattr(self, name, getattr(self, name) + getattr(other, name))


_STEP_FIELDS = tuple(f.name for f in dataclasses.fields(StepCounts))


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Outcome of scanning a nonce range."""

    nonce: int | None  # earliest hit, or None
    hashes_done: int  # how many candidates were evaluated
    steps: StepCounts | None = None  # device-stepped backends only


class _StepSpans(threading.local):
    on = True


_STEP_SPANS = _StepSpans()


@contextlib.contextmanager
def step_spans(on: bool):
    """Whether the device-stepped backends write their ``p1.search.*``
    profiler spans for searches made on this thread inside the block (on
    by default).  A node with telemetry disabled turns them off, so that
    its trace holds no span of the program."""
    was = _STEP_SPANS.on
    _STEP_SPANS.on = on
    try:
        yield
    finally:
        _STEP_SPANS.on = was


def step_spans_on() -> bool:
    return _STEP_SPANS.on


class HashBackend(abc.ABC):
    """A pluggable SHA-256d implementation."""

    #: Registry key; set by @register.
    name: str = "?"

    @abc.abstractmethod
    def sha256d(self, data: bytes) -> bytes:
        """Double SHA-256 of ``data`` (32 raw bytes out)."""

    @abc.abstractmethod
    def search(
        self, header_prefix: bytes, nonce_start: int, count: int, difficulty: int
    ) -> SearchResult:
        """Find the earliest nonce in [nonce_start, nonce_start+count) whose
        header hash meets ``difficulty`` leading zero bits.

        ``header_prefix`` is the first ``NONCE_OFFSET`` (76) bytes of the
        serialized header.  The scanned range must stay within uint32 space.
        """

    def _check_search_args(
        self, header_prefix: bytes, nonce_start: int, count: int, difficulty: int
    ) -> None:
        if len(header_prefix) != NONCE_OFFSET:
            raise ValueError(
                f"header prefix must be {NONCE_OFFSET} bytes, got {len(header_prefix)}"
            )
        if not 0 <= nonce_start <= 0xFFFFFFFF:
            raise ValueError(f"nonce_start={nonce_start} out of uint32 range")
        if count < 0 or nonce_start + count > 1 << 32:
            raise ValueError("nonce range exceeds uint32 space")
        if not 0 <= difficulty <= 255:
            raise ValueError(f"difficulty={difficulty} out of range")


_REGISTRY: dict[str, type[HashBackend]] = {}
_LAZY_BACKENDS: dict[str, Callable[[], type[HashBackend]]] = {}
_INSTANCES: dict[tuple, HashBackend] = {}


def register(name: str) -> Callable[[type[HashBackend]], type[HashBackend]]:
    """Class decorator: ``@register("cpu")`` adds the backend to the registry."""

    def deco(cls: type[HashBackend]) -> type[HashBackend]:
        if name in _REGISTRY:
            raise ValueError(f"hash backend {name!r} already registered")
        cls.name = name
        _REGISTRY[name] = cls
        # A direct `import p1_tpu.hashx.<module>` fulfills the lazy entry
        # without going through _resolve; drop it so the name isn't listed
        # twice and _resolve never re-imports a loaded module.
        _LAZY_BACKENDS.pop(name, None)
        return cls

    return deco


def register_lazy(name: str, loader: Callable[[], type[HashBackend]]) -> None:
    """Register a backend whose module should only import on first use."""
    if name in _REGISTRY or name in _LAZY_BACKENDS:
        raise ValueError(f"hash backend {name!r} already registered")
    _LAZY_BACKENDS[name] = loader


def _resolve(name: str) -> type[HashBackend]:
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name in _LAZY_BACKENDS:
        # The loader's module is expected to @register(name) on import,
        # which also removes the lazy entry.  A failed import leaves the
        # entry in place so the error surfaces again on retry.
        _LAZY_BACKENDS[name]()
        if name not in _REGISTRY:
            raise RuntimeError(f"lazy loader for {name!r} did not register it")
        return _REGISTRY[name]
    raise KeyError(
        f"unknown hash backend {name!r}; available: {sorted(available_backends())}"
    )


def get_backend(name: str, **kwargs) -> HashBackend:
    """Instantiate (and memoize) a backend by registry name."""
    key = (name, tuple(sorted(kwargs.items())))
    if key not in _INSTANCES:
        _INSTANCES[key] = _resolve(name)(**kwargs)
    return _INSTANCES[key]


def available_backends() -> Iterator[str]:
    yield from _REGISTRY
    yield from _LAZY_BACKENDS
