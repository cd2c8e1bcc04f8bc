"""Driver ``replay``: ``p1 replay``'s device rung over one long header chain.

The chain is mined once with the program's own ``generate_headers`` and
its native backend: genesis of a fixed-difficulty chain, empty merkle
roots, stamps ``parent.ts + 1``.  It follows from the traffic's
``n_headers`` and ``difficulty`` alone, never from the seed, so every
seed verifies the same work.  It is kept packed under the checkout
(``CHAINS``, named by the traffic's chain name and its parameters) and
built again when absent; the seconds spent building or loading it are a
fact of the run and part of its set-up.

The headers are held as ``BlockHeader`` objects, as a node that has
ingested them holds them, and ``chain.replay.replay_device`` verifies
the whole chain back to back, each call inside a host annotation
``perfbench.replay.<i>``: a closed loop, the next replay starting when
the last verdict is back.  Nothing is carried between calls but the
compiled program.  After the warm-up replays, the window opens at the
end of the next call and closes at the end of the first call that ends
``seconds`` or more later.

The seed draws the corruptions that ``compare`` replays after the window:
single-bit flips of one header each, in its nonce, previous hash or
difficulty, one of them always in the chain's last segment.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import pathlib
import random
import sys
import time

from perfbench import opcount, reference

SPAN = "perfbench.replay."
IDLE = ("host in replay_device: pack, upload, dispatch and readback",
        "host between replays: the harness's loop")
#: Where the chain is kept, under the checkout (git-ignored).
CHAINS = ".perfbench_chains"
#: A replay longer than this is recorded as a stall.
STALL_S = 60.0
HEADER = 80
#: Field -> (byte offset, bits that may flip).  Only the difficulty's low
#: byte flips: the header format refuses a difficulty above 255.
FIELDS = {"nonce": (76, 32), "prev_hash": (4, 256), "difficulty": (75, 8)}


@dataclasses.dataclass
class Call:
    index: int  # the call's index among this run's replays
    t0: float
    t1: float
    valid: bool
    first_invalid: int | None


@dataclasses.dataclass
class Window:
    """What a run produced; ``first`` and ``last`` index ``calls``."""

    difficulty: int
    segment: int
    raw: bytes  # the packed chain, as kept
    headers: list  # the same chain as BlockHeaders
    #: The timed path, ``verify(headers) -> ReplayReport``: the program's
    #: ``replay_device`` at the configuration's segment.  A test or the
    #: control may swap it, or patch the program beneath it.
    verify: object
    calls: list[Call] = dataclasses.field(default_factory=list)
    first: int | None = None
    last: int | None = None
    setup_s: float | None = None
    compiles_in_window: int | None = None
    phases: dict = dataclasses.field(default_factory=dict)  # set-up milestones, s
    stalls: list[str] = dataclasses.field(default_factory=list)
    chain_s: float = 0.0
    chain_built: bool = False
    native_headers_per_s: float | None = None
    corruptions: list = dataclasses.field(default_factory=list)  # set by compare

    @property
    def replays(self) -> list[Call]:
        """The calls inside the window: the answers due."""
        if self.first is None or self.last is None:
            return []
        return self.calls[self.first + 1:self.last + 1]


def chain_path(root: pathlib.Path, traffic: dict) -> pathlib.Path:
    name = f"{traffic['chain']}-n{traffic['n_headers']}-d{traffic['difficulty']}.hdr"
    return root / CHAINS / name


def load_chain(root: pathlib.Path, traffic: dict) -> tuple[bytes, bool]:
    """The packed chain and whether it was built in this run."""
    path = chain_path(root, traffic)
    size = traffic["n_headers"] * HEADER
    if path.is_file() and path.stat().st_size == size:
        return path.read_bytes(), False
    from p1_tpu.chain.replay import generate_headers, pack_headers
    from p1_tpu.hashx import get_backend

    headers = generate_headers(traffic["n_headers"], traffic["difficulty"],
                               backend=get_backend("native"))
    raw = pack_headers(headers)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    tmp.write_bytes(raw)
    os.replace(tmp, path)
    return raw, True


def drive(cell, traffic, seconds, *, rehearse, t_process, trace_dir, patch, meter):
    import jax
    from p1_tpu.chain.replay import parse_headers, replay_device, replay_native

    # The native engine builds the chain and gives the host's rate; its
    # library is kept beside the chain, so only a checkout's first run
    # compiles it.
    os.environ.setdefault("P1_NATIVE_CACHE", str(cell.root / CHAINS / "native"))
    t = time.perf_counter()
    raw, built = load_chain(cell.root, traffic)
    headers = parse_headers(raw)
    chain_s = time.perf_counter() - t
    segment = cell.config["engine"]["segment"]
    win = Window(traffic["difficulty"], segment, raw, headers,
                 functools.partial(replay_device, segment=segment),
                 chain_s=chain_s, chain_built=built)
    if patch is not None:
        patch(win)
    win.phases["chain"] = time.perf_counter() - t_process
    annotate = jax.profiler.TraceAnnotation if trace_dir is not None else None

    def call() -> Call:
        i = len(win.calls)
        t0 = time.perf_counter()
        if annotate is None:
            report = win.verify(headers)
        else:
            with annotate(f"{SPAN}{i}"):
                report = win.verify(headers)
        t1 = time.perf_counter()
        win.calls.append(Call(i, t0, t1, report.valid, report.first_invalid))
        if t1 - t0 > STALL_S:
            win.stalls.append(f"replay {i} took {t1 - t0:.1f} s")
        return win.calls[-1]

    for _ in range(traffic["warmup_replays"]):
        call()
    win.phases["warmed"] = time.perf_counter() - t_process
    tracing = False
    try:
        if trace_dir is not None:
            # The device's ops and the harness's own spans, as for ``mine``.
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 1
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing = True
        opening = call()
        win.first = opening.index
        win.setup_s = opening.t1 - t_process
        c_start = meter.compiles
        while call().t1 < opening.t1 + seconds:
            pass
        win.last = win.calls[-1].index
        win.compiles_in_window = meter.compiles - c_start
    finally:
        if tracing:
            jax.profiler.stop_trace()
    win.native_headers_per_s = replay_native(headers).headers_per_sec
    return win


def corruptions(seed: int, n: int, segment: int, k: int) -> list[tuple[int, str, int]]:
    """``k`` flips ``(index, field, bit)`` drawn from the seed, the first in
    the chain's last segment.  Genesis is never flipped: the device rung
    waives its proof of work, and its identity is the caller's to pin."""
    rng = random.Random(seed)
    last = max(1, (n - 1) // segment * segment)
    out = []
    for j in range(k):
        index = rng.randrange(last, n) if j == 0 else rng.randrange(1, n)
        field = rng.choice(sorted(FIELDS))
        out.append((index, field, rng.randrange(FIELDS[field][1])))
    return out


def flip(raw: bytes, index: int, field: str, bit: int) -> bytes:
    """``raw`` with one bit of one header's field flipped; bit 0 is the
    field's last (least significant) bit."""
    offset, bits = FIELDS[field]
    pos = index * HEADER + offset + (bits - 1 - bit) // 8
    out = bytearray(raw)
    out[pos] ^= 1 << (bit % 8)
    return bytes(out)


def compare(win, seed, devices, traffic):
    """(checks, attempted, failed), each check's limit 0:

    - ``verdict_diff``: window replays whose ``(valid, first_invalid)``
      differs from the reference's on the clean chain;
    - ``missed_corruption``: seeded flips whose ``first_invalid`` from the
      timed path differs from the reference's on the flipped chain;
    - ``chain_invalid``: 1 where the reference finds the clean chain
      itself broken (the kept chain is not the one the traffic states);
    - ``stalls``: replays that took longer than ``STALL_S``.
    """
    from p1_tpu.core.header import BlockHeader

    d = win.difficulty
    clean = reference.chain_first_invalid(win.raw, d)
    want = (clean is None, clean)
    replays = win.replays
    wrong = [c.index for c in replays if (c.valid, c.first_invalid) != want]
    missed = []
    for index, field, bit in corruptions(seed, len(win.headers), win.segment,
                                         traffic["corruptions"]):
        bad = flip(win.raw, index, field, bit)
        headers = list(win.headers)
        headers[index] = BlockHeader.deserialize(bad[index * HEADER:(index + 1) * HEADER])
        got = win.verify(headers).first_invalid
        # Headers before ``index`` are the clean chain's, which the
        # reference has checked.
        expect = clean if clean is not None and clean < index else \
            reference.chain_first_invalid(bad, d, start=index)
        win.corruptions.append([index, field, bit, got, expect])
        if got != expect:
            missed.append(index)
    values = {
        "verdict_diff": len(wrong),
        "missed_corruption": len(missed),
        "chain_invalid": int(clean is not None),
        "stalls": len(win.stalls),
    }
    checks = {name: {"value": v, "limit": 0} for name, v in values.items()}
    return checks, len(replays) + traffic["corruptions"], len(wrong) + len(missed)


@dataclasses.dataclass
class View:
    chips: int
    setup_s: float | None
    window_s: float  # from the end of the opening replay to the end of the closing one
    replays: int  # replays inside the window
    headers: int  # headers those replays verified
    trace: object  # trace.Reduced, or None
    scan_ns: float | None  # the scan's device time in the window, every device
    peak_ops_per_s: float  # one chip's 32-bit VPU operations per second
    ops_per_header: int = opcount.header_verify_ops()


def is_scan(name: str, n_segments: int, segment: int) -> bool:
    """The device trace names an op by its HLO instruction.  The scan is
    the ``while`` loop of ``verify_header_chain_segments``' ``lax.scan``,
    the one op whose state holds the whole chain, ``u32[S,segment,20]``
    (checked by hand on the chip)."""
    return name.startswith("%while") and f"u32[{n_segments},{segment},20]" in name


def scan_ns(reduced, n_segments: int, segment: int) -> float | None:
    """Union of the scan's ops on each device, summed over devices; None
    where the trace holds none, and said on standard error where it holds
    other device ops."""
    from perfbench import trace

    if reduced is None:
        return None
    total = sum(
        e - s
        for dev in reduced.devices
        for s, e in trace.union((s, e) for name, s, e in dev.ops
                                if is_scan(name, n_segments, segment)))
    if not total and any(d.ops for d in reduced.devices):
        print("perfbench: the trace holds device ops but no chain scan; the scan's "
              "readings left out", file=sys.stderr)
    return total or None


def view(win, cell, reduced, peak):
    replays = win.replays
    window_s = win.calls[win.last].t1 - win.calls[win.first].t1 if replays else 0.0
    n = len(win.headers)
    return View(
        chips=cell.chips,
        setup_s=win.setup_s,
        window_s=window_s,
        replays=len(replays),
        headers=len(replays) * n,
        trace=reduced,
        scan_ns=scan_ns(reduced, -(-n // win.segment), win.segment),
        peak_ops_per_s=peak["vpu_int32_ops_per_s"],
    )


def facts(win) -> dict:
    return {
        "n_headers": len(win.headers),
        "chain_s": win.chain_s,
        "chain_built": win.chain_built,
        "replays": len(win.replays),
        "native_headers_per_s": win.native_headers_per_s,
        "corruptions": win.corruptions,
    }


def control(win, set_attr=setattr) -> None:
    """The guarantee that every corruption is reported at its index,
    broken: the device scan skips the chain's last segment and reads it
    as clean, so the flip that always lands there goes unseen.  It
    replaces the program's ``jit_verify_chain_scan`` (``set_attr`` lets a
    test undo it)."""
    import jax.numpy as jnp
    from p1_tpu.hashx import jax_sha256

    build = jax_sha256.jit_verify_chain_scan
    if getattr(build, "skips_last_segment", False):
        return

    def skipping(n_segments, segment, platform=None, unroll=None):
        head = build(n_segments - 1, segment, platform, unroll)

        def step(words3, target, difficulty):
            clean = jnp.full((1,), segment, jnp.uint32)
            return jnp.concatenate([head(words3[:-1], target, difficulty), clean])

        return step

    skipping.skips_last_segment = True
    set_attr(jax_sha256, "jit_verify_chain_scan", skipping)
