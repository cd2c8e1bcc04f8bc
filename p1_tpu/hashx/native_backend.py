"""Native hash backend: the C++ SHA-256d core via ctypes.

Capability parity: the host-side mining tier done the way a native
reference would do it (SURVEY.md §2's rule — native components get C++
equivalents).  One C call scans a whole nonce range with the midstate
trick; on CPUs with the SHA-NI extension (runtime-dispatched inside the
.so) it ran ~10x the hashlib loop on a 1-vCPU host (not measured on the
chip host) from hardware rounds plus Python-overhead elimination.
Deterministic earliest-hit — same contract as every other backend, so it
slots into the Miner/chain/node unchanged.
"""

from __future__ import annotations

import ctypes

from p1_tpu.hashx.backend import HashBackend, SearchResult, register
from p1_tpu.hashx.native_build import build_lib


def _load():
    lib = ctypes.CDLL(str(build_lib()))
    lib.p1_sha256d.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_char_p,
    ]
    lib.p1_sha256d.restype = None
    lib.p1_search.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_uint64,
        ctypes.c_uint32,
    ]
    lib.p1_search.restype = ctypes.c_longlong
    lib.p1_has_shani.argtypes = []
    lib.p1_has_shani.restype = ctypes.c_int
    lib.p1_force_scalar.argtypes = [ctypes.c_int]
    lib.p1_force_scalar.restype = None
    lib.p1_verify_chain.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.c_int,
    ]
    lib.p1_verify_chain.restype = ctypes.c_longlong
    lib.p1_verify_chain_retarget.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.c_uint32,
    ]
    lib.p1_verify_chain_retarget.restype = ctypes.c_longlong
    return lib


def verify_header_chain(
    raw: bytes, n: int, difficulty: int, genesis_exempt: bool = True
) -> int | None:
    """Native engine for chain replay (config 3): verify ``n`` contiguous
    80-byte headers in one C call — PoW, difficulty field, prev-hash
    linkage, exactly ``chain.replay.replay_host``'s rules.  Returns the
    first invalid index, or None when the whole chain is valid."""
    if len(raw) != 80 * n:
        raise ValueError(f"expected {80 * n} header bytes, got {len(raw)}")
    idx = _lib().p1_verify_chain(raw, n, difficulty, int(genesis_exempt))
    return None if idx < 0 else int(idx)


def verify_header_chain_retarget(raw: bytes, n: int, retarget) -> int | None:
    """Retargeting form of ``verify_header_chain``: the C engine
    recomputes the contextual difficulty schedule and enforces the
    timestamp rules (strict increase + forward cap, height-1 anchor
    exempt) — rule-for-rule ``replay_host(retarget=...)``.  The caller
    validates header 0 against the chain's genesis identity, exactly as
    the host path's callers do."""
    if len(raw) != 80 * n:
        raise ValueError(f"expected {80 * n} header bytes, got {len(raw)}")
    idx = _lib().p1_verify_chain_retarget(
        raw, n, retarget.window, retarget.spacing,
        retarget.max_adjust, retarget.max_step,
    )
    return None if idx < 0 else int(idx)


_LIB = None


def _lib():
    global _LIB
    if _LIB is None:
        _LIB = _load()
    return _LIB


@register("native")
class NativeBackend(HashBackend):
    """C++ SHA-256d search (SHA-NI when the CPU has it)."""

    def __init__(self):
        self._lib = _lib()
        self.has_shani = bool(self._lib.p1_has_shani())

    def force_scalar(self, enable: bool) -> None:
        """Test hook: pin the portable scalar compression on/off."""
        self._lib.p1_force_scalar(int(enable))
        self.has_shani = bool(self._lib.p1_has_shani())

    def sha256d(self, data: bytes) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._lib.p1_sha256d(data, len(data), out)
        return out.raw

    def search(
        self, header_prefix: bytes, nonce_start: int, count: int, difficulty: int
    ) -> SearchResult:
        self._check_search_args(header_prefix, nonce_start, count, difficulty)
        hit = self._lib.p1_search(header_prefix, nonce_start, count, difficulty)
        if hit < 0:
            return SearchResult(None, count)
        return SearchResult(int(hit), int(hit) - nonce_start + 1)
