"""Ed25519 account keys: ownership for the account model.

Capability parity: the reference is "a Bitcoin-like toy cryptocurrency"
(BASELINE.json:5) — "only the owner can spend" is the property that makes
a ledger mean anything.  Design (tpu rebuild, round 4):

- An **account id is a key fingerprint**: ``p1`` + first 16 hex chars of
  SHA-256(public key).  Any string can *receive* coins (miner ids stay
  free-form; coins sent to a non-fingerprint id are simply unspendable),
  but only a transaction carrying the matching public key and a valid
  Ed25519 signature can *spend* from a fingerprint account — enforced at
  mempool admission AND block validation (p1_tpu/chain/validate.py).
- Ed25519 via the ``cryptography`` package **when the wheel is present**,
  else the vendored pure-Python RFC 8032 implementation
  (core/_ed25519.py).  The wheel is an optional accelerator, never an
  import-time requirement: images without it (no egress to fetch one)
  still import, sign, and verify — byte-identically, just slower.
  Signatures are 64 bytes, public keys 32 — both fit the transaction's
  length-prefixed layout.
- Deterministic from a 32-byte seed, so tests can use fixed keys and the
  CLI can persist one JSON file per identity (``p1 keygen``).

Validation fast lane (rounds 8 and 15).  Ed25519 verify costs ~100 µs
with the wheel and ~3 ms pure-Python, and it dominates every untrusted
validation path, so this module carries a backend LADDER plus three
speed layers on top of the plain ``verify``:

- **Backend ladder** (round 15): ``cryptography`` wheel > native C++
  engine (native/ed25519.cpp via core/_ed25519_native.py — built
  lazily, content-addressed, ~20× the pure-Python fallback on this
  host) > pure-Python ``core/_ed25519.py``.  Resolution is lazy and
  memoized (``backend()``); a missing wheel, missing compiler, or
  failed build degrades one rung with a single log line and identical
  semantics — every backend is pinned verdict- and error-text-
  equivalent on every input by the torsion/corruption equivalence
  matrix (tests/test_sigbatch.py, tests/test_native_ed25519.py).
  ``set_sig_backend`` / ``NodeConfig.sig_backend`` / ``--sig-backend``
  / ``P1_SIG_BACKEND`` force a rung (``fallback`` = pure-Python), or
  opt batches into the ``device`` tier — the JAX multi-scalar
  multiplication sharded over the chip mesh
  (hashx/ed25519_msm.py, a win on real TPU meshes, not on host CPUs).
- ``verify_batch(triples)`` — verify many (pubkey, sig, message) triples
  at once.  With the ``cryptography`` wheel the triples are chunked over
  a ``concurrent.futures`` thread pool (``set_verify_workers`` /
  ``config.verify_workers``; OpenSSL releases the GIL, so threads give
  real parallelism on multi-core) — the native engine's chunks use the
  same pool (ctypes releases the GIL during the C call).  On the
  pure-Python rung the fallback uses a genuine batch-verification
  equation — one multi-scalar multiplication for the whole window plus
  an exact prime-subgroup gate on every point
  (``_ed25519.verify_batch``), ~2× per signature at revalidation window
  sizes — run in the calling thread (it holds the GIL, so a pool would
  add overhead, not parallelism) and chunked so memory stays bounded.
  The native and device batches compute the SAME subgroup-gated
  equation.  Batch TRUE implies every triple is serially valid; batch
  FALSE is not yet a verdict (the gate also rejects torsion-crafted
  inputs the serial equation tolerates).
- ``first_invalid(triples)`` — serial-confirming locator used when a
  batch fails: sub-batches that pass are skipped (acceptance implies
  serial validity), everything else is settled by ``verify`` itself, so
  the REJECTED signature (and the error text consensus reports) — or
  the conclusion that there is none — is byte-identical to the serial
  path's.
- The verify-once signature cache lives one level up
  (core/sigcache.py, keyed by txid) — positive results are memoized
  there, never here.  ``verify`` keeps only a small bounded NEGATIVE
  memo (deterministic function, so semantics-free) to absorb peers
  replaying a known-bad signature; ``STATS`` counts how work reached
  the backend (serial vs batched) for ``status()["validation"]`` and
  the no-double-verify regression tests.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import json
import logging
import os
import threading

try:  # pragma: no cover - exercised implicitly by whichever env runs
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric import ed25519

    HAVE_CRYPTOGRAPHY = True
except ImportError:  # the wheel is optional; fall back to pure Python
    HAVE_CRYPTOGRAPHY = False

from p1_tpu.core import _ed25519 as _py_ed25519
from p1_tpu.core import _ed25519_native as _native_ed25519

#: Account-id prefix: distinguishes spendable (key-backed) accounts from
#: free-form receive-only ids at a glance.
ACCOUNT_PREFIX = "p1"
_FINGERPRINT_HEX = 16

PUBKEY_SIZE = 32
SIG_SIZE = 64
SEED_SIZE = 32


@functools.lru_cache(maxsize=65_536)
def account_id(pubkey: bytes) -> str:
    """The spendable account id owned by ``pubkey``.  Memoized: every
    ``verify_signature`` call derives the sender's fingerprint, and a
    node re-checks the same few senders' keys across gossip admission,
    block validation, and reorgs — pure function, bounded cache."""
    if len(pubkey) != PUBKEY_SIZE:
        raise ValueError(f"public key must be {PUBKEY_SIZE} bytes")
    return ACCOUNT_PREFIX + hashlib.sha256(pubkey).hexdigest()[:_FINGERPRINT_HEX]


def account_id_or_none(pubkey: bytes) -> str | None:
    """``account_id`` that maps a malformed key to None (never a valid
    sender id) instead of raising — for use in validation predicates."""
    return account_id(pubkey) if len(pubkey) == PUBKEY_SIZE else None


class Keypair:
    """One Ed25519 identity: seed -> (private, public, account id)."""

    def __init__(self, seed: bytes):
        if len(seed) != SEED_SIZE:
            raise ValueError(f"seed must be {SEED_SIZE} bytes")
        self._seed = seed
        if HAVE_CRYPTOGRAPHY:
            self._private = ed25519.Ed25519PrivateKey.from_private_bytes(seed)
            self.pubkey: bytes = self._private.public_key().public_bytes_raw()
        else:
            self._private = None
            self.pubkey = _py_ed25519.public_key(seed)
        self.account: str = account_id(self.pubkey)

    @classmethod
    def generate(cls) -> "Keypair":
        return cls(os.urandom(SEED_SIZE))

    @classmethod
    def from_seed_text(cls, text: str) -> "Keypair":
        """Deterministic keypair from any text label (tests/tools only —
        the seed is the SHA-256 of the label, so the 'secret' is public)."""
        return cls(hashlib.sha256(text.encode("utf-8")).digest())

    def sign(self, message: bytes) -> bytes:
        if self._private is not None:
            return self._private.sign(message)
        # Ed25519 signing is deterministic (RFC 8032): the fallback
        # produces the exact bytes the wheel would.
        return _py_ed25519.sign(self._seed, message)

    # -- persistence (p1 keygen / p1 tx --key) ---------------------------

    def save(self, path: str, overwrite: bool = False) -> None:
        """Write the key as JSON {seed_hex, pubkey_hex, account} with
        owner-only permissions (it contains the private seed).

        Refuses to clobber an existing file unless ``overwrite`` — a seed
        exists nowhere else, so silently truncating one would make every
        coin its fingerprint holds permanently unspendable.
        """
        payload = json.dumps(
            {
                "seed_hex": self._seed.hex(),
                "pubkey_hex": self.pubkey.hex(),
                "account": self.account,
            },
            indent=2,
        )
        flags = os.O_WRONLY | os.O_CREAT | (
            os.O_TRUNC if overwrite else os.O_EXCL
        )
        fd = os.open(path, flags, 0o600)
        # os.open's mode only applies at creation — an overwrite of a
        # pre-existing world-readable file must still end up owner-only.
        os.fchmod(fd, 0o600)
        with os.fdopen(fd, "w") as f:
            f.write(payload + "\n")

    @classmethod
    def load(cls, path: str) -> "Keypair":
        with open(path) as f:
            data = json.load(f)
        kp = cls(bytes.fromhex(data["seed_hex"]))
        if data.get("account") not in (None, kp.account):
            raise ValueError(
                f"key file {path} claims account {data['account']} but its "
                f"seed derives {kp.account}"
            )
        return kp


log = logging.getLogger(__name__)

#: Every signature backend this module can resolve, in ladder order.
#: ``device`` is batch-only (hashx/ed25519_msm.py) and never enters
#: auto-resolution — it is an explicit opt-in for real device meshes.
SIG_BACKENDS = ("cryptography", "native", "pure-python", "device")

#: Explicit backend override (``set_sig_backend``); None = auto ladder.
_sig_backend: str | None = None
#: Memoized auto/override resolution (native probing compiles once).
_resolved: str | None = None


def set_sig_backend(name: str | None) -> None:
    """Pin the signature backend: ``auto``/None resolves the ladder
    (wheel > native > pure-Python), ``cryptography``/``native`` force a
    rung (falling back down the ladder with one warning if the rung is
    unavailable), ``fallback``/``pure-python`` force the pure-Python
    tier, ``device`` routes BATCHES through the JAX mesh MSM (serial
    verifies keep the auto ladder — the device path only pays off at
    window sizes) and raises, never degrades, when no device is usable.
    Unknown names raise (a typo must not silently change the validation
    cost model)."""
    global _sig_backend, _resolved
    if name in (None, "", "auto"):
        _sig_backend = None
    elif name == "fallback":
        _sig_backend = "pure-python"
    elif name in SIG_BACKENDS:
        _sig_backend = name
    else:
        raise ValueError(
            f"unknown signature backend {name!r} "
            f"(choose from auto/fallback/{'/'.join(SIG_BACKENDS)})"
        )
    _resolved = None


def backend() -> str:
    """The ACTIVE serial-verification backend name, resolved lazily.

    Resolution is memoized: probing the native rung may compile the
    shared object once (content-addressed cache), and a failed probe is
    remembered so a compiler-less image pays one attempt, not one per
    call.  ``device`` overrides report ``device`` (that is where batch
    work goes) while serial dispatch underneath keeps the auto ladder.
    """
    global _resolved
    if _resolved is not None:
        return _resolved
    want = _sig_backend
    if want is None:
        want = os.environ.get("P1_SIG_BACKEND") or "auto"
        if want == "fallback":
            want = "pure-python"
        if want not in SIG_BACKENDS and want != "auto":
            log.warning("P1_SIG_BACKEND=%r unknown; using auto", want)
            want = "auto"
    if want == "cryptography" and not HAVE_CRYPTOGRAPHY:
        log.warning(
            "signature backend 'cryptography' requested but the wheel is "
            "absent; resolving the auto ladder instead"
        )
        want = "auto"
    if want == "native" and not _native_ed25519.available():
        log.warning(
            "signature backend 'native' requested but the engine did not "
            "load (no compiler / build failure); resolving the auto ladder"
        )
        want = "auto"
    if want == "auto":
        if HAVE_CRYPTOGRAPHY:
            want = "cryptography"
        elif _native_ed25519.available():
            want = "native"
        else:
            want = "pure-python"
    _resolved = want
    return want


def backend_label() -> str:
    """The backend name WITHOUT resolving: the memoized rung if any
    verification already ran, else the pin/env request verbatim.

    Status planes read this instead of ``backend()`` because resolution
    may probe the native rung — a ctypes load that can compile the
    shared object once — and a GETSTATUS served from the node's event
    loop must never be the call that pays it.  The only divergence from
    ``backend()`` is a node that has verified nothing yet, which
    reports the request (``auto``/pin) rather than forcing the probe.
    """
    if _resolved is not None:
        return _resolved
    if _sig_backend is not None:
        return _sig_backend
    want = os.environ.get("P1_SIG_BACKEND") or "auto"
    return want if want in SIG_BACKENDS else "auto"


def _serial_backend() -> str:
    """Where one-at-a-time verifies go: the active backend, except that
    ``device`` is batch-only and serial work takes the ladder beneath."""
    b = backend()
    if b != "device":
        return b
    if HAVE_CRYPTOGRAPHY:
        return "cryptography"
    return "native" if _native_ed25519.available() else "pure-python"


@dataclasses.dataclass
class VerifyStats:
    """Process-wide backend-call accounting.  ``serial`` counts
    signatures that reached the backend one at a time, ``batched`` the
    ones that went through ``verify_batch`` — together they are the
    node's "how much Ed25519 did we actually pay for" figure, and the
    no-double-verify regression tests assert their deltas are zero on
    cache-hit paths (a cache hit touches neither counter).
    ``backends`` splits the same signature counts by the backend that
    did the work (``status()["validation"]["backends"]``, the
    MetricsRegistry export) — the key set is FIXED so the status wire
    contract stays byte-pinnable."""

    serial: int = 0
    batched: int = 0
    batches: int = 0
    pool_dispatches: int = 0
    backends: dict = dataclasses.field(
        default_factory=lambda: {name: 0 for name in SIG_BACKENDS}
    )

    def reset(self) -> None:
        self.serial = self.batched = self.batches = self.pool_dispatches = 0
        for name in self.backends:
            self.backends[name] = 0


STATS = VerifyStats()


def _backend_verify(pubkey: bytes, sig: bytes, message: bytes) -> bool:
    """THE single-signature backend dispatch — every serial verify in
    the process funnels through here (tests spy on it)."""
    STATS.serial += 1
    which = _serial_backend()
    STATS.backends[which] += 1
    if which == "cryptography":
        try:
            ed25519.Ed25519PublicKey.from_public_bytes(pubkey).verify(
                sig, message
            )
            return True
        except (InvalidSignature, ValueError):
            return False
    if which == "native":
        return _native_ed25519.verify(pubkey, sig, message)
    return _py_ed25519.verify(pubkey, sig, message)


#: Bounded negative-verify memo.  Positive results are memoized at the
#: transaction layer (core/sigcache.py, keyed by txid); without a
#: negative counterpart, a peer replaying the same invalid tx or block
#: forces a full backend verify every time (~3 ms on the pure-Python
#: backend) where the pre-round-8 lru_cache was O(1).  Failures only:
#: ``verify`` is a deterministic function of the three byte strings, so
#: memoizing a FALSE can never change an outcome, and the key is a
#: salted digest of the exact bytes so an entry can't shadow any other
#: (pubkey, sig, message).  Single-threaded by design, like sigcache:
#: consulted on the event-loop/serial paths only — pool workers go
#: through ``_verify_chunk``, which never touches it.
_NEG_CACHE_MAX = 4096
_neg_salt = os.urandom(16)
_neg_cache: collections.OrderedDict = collections.OrderedDict()


def _neg_key(pubkey: bytes, sig: bytes, message: bytes) -> bytes:
    h = hashlib.sha256(_neg_salt)
    h.update(pubkey)
    h.update(sig)
    h.update(message)
    return h.digest()[:16]


def verify(pubkey: bytes, sig: bytes, message: bytes) -> bool:
    """True iff ``sig`` is ``pubkey``'s valid Ed25519 signature over
    ``message``.  A deterministic function of the three byte strings;
    the verify-once memo for VALID signatures lives at the transaction
    layer (core/sigcache.py, keyed by txid), and known-bad triples are
    absorbed by the bounded negative memo above — a memo hit touches no
    STATS counter because no backend work happened."""
    if len(pubkey) != PUBKEY_SIZE or len(sig) != SIG_SIZE:
        return False
    key = _neg_key(pubkey, sig, message)
    if key in _neg_cache:
        _neg_cache.move_to_end(key)
        return False
    ok = _backend_verify(pubkey, sig, message)
    if not ok:
        _neg_cache[key] = None
        while len(_neg_cache) > _NEG_CACHE_MAX:
            _neg_cache.popitem(last=False)
    return ok


# -- batch verification (untrusted-path fast lane, round 8) --------------

#: Below this many cache-missing signatures a batch call just runs
#: serially: thread dispatch and the MSM setup both cost more than they
#: save on a handful of signatures.  A constant, NOT configuration —
#: validation behavior must not vary with local tuning.
BATCH_MIN = 8

#: Signatures per worker chunk (wheel path) / per MSM window (fallback).
#: Bounds both the pool task granularity and the fallback's per-window
#: memory; the MSM's per-signature gain is nearly flat past ~1k.
BATCH_CHUNK = 1024

_workers_lock = threading.Lock()
_workers: int | None = None  # explicit set_verify_workers override
_executor = None
_executor_size = 0
_fallback_warned = False


def set_verify_workers(n: int | None) -> None:
    """Pin the verification worker-pool size (None/0 = auto: the
    ``P1_VERIFY_WORKERS`` env var, else ``os.cpu_count()``).  Takes
    effect on the next batch; an existing pool of a different size is
    drained and replaced lazily."""
    global _workers
    _workers = int(n) if n else None


def verify_workers() -> int:
    """The resolved worker count batches will use."""
    if _workers is not None:
        return max(1, _workers)
    env = os.environ.get("P1_VERIFY_WORKERS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return max(1, os.cpu_count() or 1)


def shutdown_verify_pool(cancel: bool = False) -> None:
    """Tear down the lazy worker pool (tests, interpreter exit).  Safe
    to call any time: in-flight batches fall back to in-thread
    verification when their futures are cancelled."""
    global _executor, _executor_size
    with _workers_lock:
        ex, _executor, _executor_size = _executor, None, 0
    if ex is not None:
        ex.shutdown(wait=not cancel, cancel_futures=cancel)


def _pool(size: int):
    """The shared verification executor, (re)built at ``size``."""
    global _executor, _executor_size
    with _workers_lock:
        if _executor is None or _executor_size != size:
            old = _executor
            from concurrent.futures import ThreadPoolExecutor

            _executor = ThreadPoolExecutor(
                max_workers=size, thread_name_prefix="sigverify"
            )
            _executor_size = size
        else:
            old = None
    if old is not None:
        old.shutdown(wait=False, cancel_futures=True)
    return _executor


def _verify_chunk(triples) -> bool:
    """Serial chunk worker: exact single-signature semantics.  Used by
    the wheel path (OpenSSL releases the GIL, so chunks verify in
    parallel) and as the cancellation fallback everywhere."""
    for pubkey, sig, message in triples:
        if len(pubkey) != PUBKEY_SIZE or len(sig) != SIG_SIZE:
            return False
        if not HAVE_CRYPTOGRAPHY:
            if not _py_ed25519.verify(pubkey, sig, message):
                return False
            continue
        try:
            ed25519.Ed25519PublicKey.from_public_bytes(pubkey).verify(
                sig, message
            )
        except (InvalidSignature, ValueError):
            return False
    return True


def _batch_worker():
    """``(backend_name, callable)`` one batch CHUNK runs through —
    resolved per batch so ``set_sig_backend`` takes effect immediately.
    Every worker computes the same subgroup-gated contract (or, for the
    wheel, exact per-signature serial checks, which is strictly
    stronger than 'batch TRUE implies serial TRUE')."""
    b = backend()
    if b == "device":
        # An explicit device pin never degrades: with no usable JAX
        # device the import or the mesh raises, and the caller sees it.
        from p1_tpu.hashx import ed25519_msm

        return "device", ed25519_msm.verify_batch_device
    if b == "cryptography":
        return "cryptography", _verify_chunk
    if b == "native":
        return "native", _native_ed25519.verify_batch
    return "pure-python", _py_ed25519.verify_batch


def _use_pool(n_chunks: int) -> bool:
    """Whether a batch's chunks go to the thread pool.  The wheel and
    native paths benefit: OpenSSL releases the GIL inside each verify
    and ctypes releases it around the native batch call, so chunks
    genuinely overlap on multi-core.  The pure-Python fallback holds
    the GIL for its whole MSM — dispatching it to workers buys no
    parallelism, just executor overhead and pool churn — so fallback
    chunks run in the calling thread; the device path schedules its own
    mesh and must not be double-dispatched.  Tests monkeypatch this to
    force the pool and exercise its shutdown/cancellation machinery
    without the wheel."""
    return (
        backend() in ("cryptography", "native")
        and n_chunks > 1
        and verify_workers() > 1
    )


def _warn_fallback_once() -> None:
    """One-time cost-model warning when batches run on the pure-Python
    rung, naming the FASTEST backend this host could offer instead —
    so no-wheel numbers are never read as regressions, and an operator
    who merely lacks the toolchain learns the native rung exists."""
    global _fallback_warned
    if _fallback_warned:
        return
    _fallback_warned = True
    if _sig_backend == "pure-python":
        fastest = (
            "the pure-Python fallback was FORCED via "
            "--sig-backend/P1_SIG_BACKEND; 'auto' would pick a faster rung"
        )
    elif _native_ed25519.available():
        # Reachable only by forcing pure-python off a native-capable
        # host, handled above — kept for the belt-and-braces case.
        fastest = "the native C++ engine is available on this host"
    else:
        fastest = (
            "fastest available here; the native C++ engine needs only a "
            "C++ toolchain, and the `cryptography` wheel neither"
        )
    log.warning(
        "pure-Python Ed25519 fallback is the active backend for batch "
        "verification, the slowest rung — %s.  Numbers measured on this "
        "rung are NOT comparable to the native engine's or the wheel's.",
        fastest,
    )


def verify_batch(triples) -> bool:
    """True only if EVERY (pubkey, sig, message) triple is serially
    valid (batch acceptance implies serial acceptance).

    False means "not proven": usually a bad signature, but the gated
    batches also reject torsion-crafted inputs the serial equation
    tolerates (_ed25519.py's docstring) — use ``first_invalid`` to
    settle a failed batch with serial-identical semantics.
    Dispatch (``_batch_worker``): wheel → per-signature verifies
    chunked across the worker pool (exact serial semantics, parallel on
    multi-core); native → the C++ subgroup-gated batch per chunk, also
    pool-parallel (ctypes releases the GIL); pure-Python → the fallback
    MSM per chunk in the calling thread; device (opt-in) → the JAX mesh
    MSM (hashx/ed25519_msm.py).
    """
    triples = list(triples)
    if not triples:
        return True
    STATS.batches += 1
    STATS.batched += len(triples)
    which, worker = _batch_worker()
    if which == "pure-python":
        _warn_fallback_once()
    if len(triples) < BATCH_MIN:
        STATS.batched -= len(triples)  # accounted as serial below
        return _verify_serial_counted(triples)
    STATS.backends[which] += len(triples)
    chunks = [
        triples[i : i + BATCH_CHUNK]
        for i in range(0, len(triples), BATCH_CHUNK)
    ]
    if not _use_pool(len(chunks)):
        return all(worker(chunk) for chunk in chunks)
    n = verify_workers()
    from concurrent.futures import CancelledError

    STATS.pool_dispatches += 1
    pool = _pool(n)
    futures = []
    for chunk in chunks:
        try:
            futures.append(pool.submit(worker, chunk))
        except RuntimeError:
            # Pool shut down mid-submission: the rest verify in-thread.
            futures.append(None)
    ok = True
    for fut, chunk in zip(futures, chunks):
        if fut is None:
            ok &= worker(chunk)
            continue
        try:
            ok &= fut.result()
        except (CancelledError, RuntimeError):
            # Pool torn down mid-batch (shutdown, interpreter exit):
            # finish in this thread — the answer must not depend on
            # executor lifecycle.
            ok &= worker(chunk)
    return ok


def _verify_serial_counted(triples) -> bool:
    for pubkey, sig, message in triples:
        if not verify(pubkey, sig, message):
            return False
    return True


def first_invalid(triples) -> int | None:
    """Index of the FIRST triple serial verification rejects, or None.

    None after a failed ``verify_batch`` is a legitimate answer —
    batch False does not imply a serial reject (the fallback's subgroup
    gate also turns away torsion-crafted inputs the serial equation
    tolerates), so callers must treat None as "all serially valid".
    Left-first scan: a sub-batch that PASSES proves all its members
    serially valid and is skipped wholesale; a sub-batch that fails is
    split, and windows of ≤ BATCH_MIN are settled one by one with
    ``verify`` itself — so the identified signature (or the conclusion
    that none exists) is exactly what the serial path would produce.
    The old bisection assumed "batch failed ⇒ a serial reject inside",
    which the gate broke: a torsion reject in one half would steer the
    search away from a genuinely bad signature in the other.
    """
    triples = list(triples)

    def scan(lo: int, hi: int, known_failed: bool) -> int | None:
        if hi - lo <= BATCH_MIN:
            for i in range(lo, hi):
                if not verify(*triples[i]):
                    return i
            return None
        if not known_failed and verify_batch(triples[lo:hi]):
            return None
        mid = (lo + hi) // 2
        found = scan(lo, mid, False)
        return found if found is not None else scan(mid, hi, False)

    # Callers reach here right after a failed full batch: don't re-run it.
    return scan(0, len(triples), True)


def __getattr__(name: str):
    # Round-15 compat: ``BACKEND`` was a module constant when the
    # ladder had two fixed rungs; with lazy native resolution it is a
    # function (``backend()``).  Old importers keep working — the
    # attribute read resolves the ladder at that moment.
    if name == "BACKEND":
        return backend()
    raise AttributeError(name)
