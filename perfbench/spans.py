"""The program's own spans in a profiler trace, read beside the device's ops.

The node writes its mining path into the JAX profiler's trace as host
annotations named ``p1.*`` (``p1.mine.search``, ``p1.search.dispatch``,
``p1.stage.store``, ...), on the clock the device ops share, with their
metadata as stats: ``height`` on one block's spans, ``launch`` (an id per
backend, in dispatch order) and ``nonces`` on the host loop's.  From them
and the device trace this module computes:

- ``idle_by_span``: each device idle gap of the window split exactly
  among the innermost program span open at each instant on any host
  thread (the one that began last); time no span covers goes to
  ``no program span``.  The rows sum to the window's idle time.
- ``join``: each device's kernel ops matched to the dispatch spans first
  in, first out (a device runs its ops in launch order).  An op that
  starts before the first unmatched dispatch began was launched before
  the trace did; its id is the one before, by the ids' order.  A launch
  no ``p1.search.readback`` names was abandoned: the step in flight
  behind a hit.
- the window's abandoned kernel share, post-seal and host-loop time a
  block, and the launch counts to set beside the program's counters.

The benchmark's own run (``perfbench.run``) does not call this module yet
(PERF.md, Open questions).  Run it on the chip as

    python3 -m perfbench.spans --workload <cell> --seed <n> --seconds <s>

which drives one traced window of the cell the way ``perfbench.run --trace
1`` does and prints one JSON line; ``--rehearse`` runs it on the CPU at
rehearsal sizes, where the trace holds no device and only the host's
numbers print.
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import warnings

PREFIX = "p1."
NO_SPAN = "no program span"
DISPATCH = "p1.search.dispatch"
READBACK = "p1.search.readback"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    stats: dict


def load(log_dir: str) -> dict:
    """The ``p1.*`` host events of the newest ``.xplane.pb`` under
    ``log_dir`` as plain data: ``{"spans": [[name, start_ns, duration_ns,
    stats], ...]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    with warnings.catch_warnings():  # jaxlib's stats type warns when listed
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(paths[-1]).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        out.append([e.name, e.start_ns, e.duration_ns, dict(e.stats)])
    return {"spans": out}


def spans(data: dict) -> list[Span]:
    """``load``'s plain data as spans, by start."""
    return sorted((Span(n, s, s + d, st) for n, s, d, st in data["spans"]),
                  key=lambda sp: sp.start)


def clipped_ns(found: list[Span], names, t0: float, t1: float) -> float:
    """Time the named spans cover inside ``[t0, t1]``, summed."""
    return sum(max(0.0, min(sp.end, t1) - max(sp.start, t0))
               for sp in found if sp.name in names)


def segments(found: list[Span], t0: float, t1: float) -> list[tuple[float, float, str]]:
    """``[t0, t1]`` cut where the innermost open span changes:
    ``(start, end, name)`` pieces that tile the window."""
    inside = [sp for sp in found if sp.end > t0 and sp.start < t1]
    points = sorted({t0, t1, *(max(sp.start, t0) for sp in inside),
                     *(min(sp.end, t1) for sp in inside)})
    by_start = sorted(inside, key=lambda sp: sp.start)
    heap: list = []  # (-start, end, order, name): the latest begun on top
    nxt = 0
    out: list[tuple[float, float, str]] = []
    for a, b in zip(points, points[1:]):
        while nxt < len(by_start) and by_start[nxt].start <= a:
            sp = by_start[nxt]
            heapq.heappush(heap, (-sp.start, sp.end, nxt, sp.name))
            nxt += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        # A span that began later but ended sooner is popped when on top;
        # one below an open span waits, and is popped once it surfaces.
        name = heap[0][3] if heap else NO_SPAN
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_span(reduced, found: list[Span], t0: float, t1: float) -> list[list]:
    """``[[span name, seconds], ...]``, largest first: each device's idle
    gaps split among the innermost program span open at each instant,
    mean over devices, as ``Reduced.breakdown`` gives ``idle_gaps``."""
    pieces = segments(found, t0, t1)
    n = len(reduced.devices)
    rows: dict[str, float] = {}
    for dev in reduced.devices:
        i = 0
        for s, e in dev.gaps:
            while i < len(pieces) and pieces[i][1] <= s:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < e:
                a, b, name = pieces[j]
                rows[name] = rows.get(name, 0.0) + (min(b, e) - max(a, s)) / n
                j += 1
    return [[k, v / 1e9] for k, v in sorted(rows.items(), key=lambda kv: -kv[1])]


@dataclasses.dataclass
class Joined:
    """One device's kernel ops with their launches."""

    ops: list  # (start, end, launch id or None)
    read_back: set  # launch ids some readback span names
    unmatched: int  # ops matched to no dispatch span

    def abandoned(self, launch) -> bool:
        return launch is not None and launch not in self.read_back


def join(kernel_ops, found: list[Span]) -> Joined:
    """Match one device's kernel ops ``[(start, end), ...]`` to the
    dispatch spans, first in, first out, by launch id."""
    dispatched = sorted((sp for sp in found if sp.name == DISPATCH),
                        key=lambda sp: sp.stats["launch"])
    read_back = {sp.stats["launch"] for sp in found if sp.name == READBACK}
    ops, before, p = [], 0, 0
    for s, e in sorted(kernel_ops):
        if p < len(dispatched) and s >= dispatched[p].start:
            ops.append((s, e, dispatched[p].stats["launch"]))
            p += 1
        else:
            ops.append((s, e, None))
            before += p == 0
    if dispatched and before:
        # Launched before the trace began: the ids just below the first
        # traced one, in order.
        first = dispatched[0].stats["launch"]
        ops[:before] = [(s, e, first - before + k) for k, (s, e, _) in enumerate(ops[:before])]
    return Joined(ops, read_back, sum(1 for *_, i in ops if i is None))


def kernel_ops(plane: dict) -> list[tuple[float, float]]:
    """A device plane's kernel ops, whole, as ``(start, end)``."""
    from perfbench import layers, trace

    return [(s, s + d) for name, s, d in trace.ops(plane) if layers.is_kernel(name)]


def abandoned_kernel_share(joined: list[Joined], t0: float, t1: float) -> float | None:
    """Kernel time of abandoned launches over all kernel time inside
    ``[t0, t1]``, every device, in %."""
    total = lost = 0.0
    for j in joined:
        for s, e, launch in j.ops:
            d = max(0.0, min(e, t1) - max(s, t0))
            total += d
            if j.abandoned(launch):
                lost += d
    return 100.0 * lost / total if total else None


def report(tr: dict, reduced, data: dict, first: int, last: int, blocks: int) -> dict:
    """Everything this module reads from one traced window: the trace
    (``trace.load``), its reduction, the spans (``load``) and the window's
    edges (search calls ``first`` and ``last``) and block count."""
    from perfbench import trace
    from perfbench.drivers import mine

    edges = trace.search_spans(tr, mine.SPAN)
    t0, t1 = edges[first][1], edges[last][1]
    found = spans(data)
    per_block = lambda names: (clipped_ns(found, names, t0, t1) / 1e6 / blocks  # noqa: E731
                               if blocks else None)
    out = {
        "post_seal_ms_per_block": per_block({"p1.mine.post_seal"}),
        "search_host_ms_per_block": per_block({"p1.search.prepare", DISPATCH}),
        "spans_ms_per_block": {
            name: per_block({name}) for name in sorted({sp.name for sp in found})},
        "window_dispatches": sum(1 for sp in found if sp.name == DISPATCH and t0 <= sp.start < t1),
    }
    if reduced is not None:
        joined = [join(kernel_ops(plane), found) for _, plane in trace.device_planes(tr)]
        dev0 = [(s, e, i) for s, e, i in joined[0].ops if e > t0 and s < t1]
        out["abandoned_kernel_share"] = abandoned_kernel_share(joined, t0, t1)
        out["window_kernel_ops_device0"] = len(dev0)
        out["window_kernel_ops_abandoned_device0"] = sum(
            1 for *_, i in dev0 if joined[0].abandoned(i))
        out["window_kernel_ops_unmatched"] = sum(
            1 for j in joined for s, e, i in j.ops if i is None and e > t0 and s < t1)
        idle = sum(sum(e - s for s, e in d.gaps) for d in reduced.devices) / len(reduced.devices)
        out["idle_s"] = idle / 1e9
        out["idle_by_span"] = idle_by_span(reduced, found, t0, t1)
    return out


def main(argv=None) -> int:
    import argparse
    import asyncio
    import json
    import os
    import pathlib
    import shutil
    import sys
    import tempfile
    import time

    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true", help="CPU, rehearsal sizes")
    args = ap.parse_args(argv)
    from perfbench import cell as cells

    root = pathlib.Path(__file__).resolve().parent.parent
    cell = cells.load(args.workload, root)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1 and "device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                       f" --xla_force_host_platform_device_count={cell.chips}")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    if devices[0].platform != want or len(devices) < cell.chips:
        print(f"perfbench.spans: cell {cell.name} needs {cell.chips} {want} device(s); "
              f"JAX found {len(devices)} {devices[0].platform!r}", file=sys.stderr)
        return 2
    from perfbench import trace
    from perfbench.compile_meter import CompileMeter
    from perfbench.drive import drive
    from perfbench.drivers import mine

    traffic = dict(cell.traffic, **(cell.traffic["rehearsal"] if args.rehearse else {}))
    calls = []  # each search_nonce call's MineStats, in call order

    def record_stats(node):
        search = node.miner.search_nonce

        def recorded(header, abort=None, start_nonce=0):
            try:
                return search(header, abort, start_nonce)
            finally:
                calls.append(node.miner.last_stats)

        node.miner.search_nonce = recorded

    trace_dir = tempfile.mkdtemp(prefix="perfbench-spans-")
    try:
        win = asyncio.run(drive(
            cell, traffic["chain"], args.seconds, difficulty=traffic["difficulty"],
            warmup_blocks=traffic["warmup_blocks"], rehearse=args.rehearse,
            t_process=t_process, trace_dir=trace_dir, patch=record_stats,
            meter=CompileMeter()))
        if win.first is None or win.last is None:
            print(f"perfbench.spans: no window: {win.stalls}", file=sys.stderr)
            return 1
        tr, data = trace.load(trace_dir), load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    reduced = trace.reduce(tr, mine.SPAN, win.first, win.last, mine.IDLE)
    out = {"workload": cell.name, "seed": args.seed,
           "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                      "count": cell.chips},
           "blocks": len(win.blocks)}
    out.update(report(tr, reduced, data, win.first, win.last, len(win.blocks)))
    inside = calls[win.first + 1:win.last + 1]
    out["program"] = {f: sum(getattr(s, f) for s in inside) for f in
                      ("launches", "launches_abandoned", "nonces_launched", "nonces_abandoned",
                       "prepare_s", "dispatch_s", "readback_s")}
    if reduced is not None:
        out["window_s"] = reduced.window_ns / 1e9
        out["breakdown"] = reduced.breakdown()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
