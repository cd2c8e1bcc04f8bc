"""From a profiler trace to the numbers the per-layer readers take.

``load`` turns JAX's ``.xplane.pb`` into plain data: planes, their lines,
and events ``[name, start_ns, duration_ns]``, all on the one clock the
profiler gives them.  ``reduce`` then works on that plain form only, so
``tests/test_trace.py`` checks it on a small trace recorded on the chip
and kept as JSON.

The window is marked by the harness itself: every timed call of the
cell's driver runs inside a host annotation ``<span><i>`` (``i`` = the
call's index; ``perfbench.search.`` for the miner's ``search_nonce``),
and the window runs from the end of the call that opened it to the end
of the call that closed it.
"""

from __future__ import annotations

import dataclasses
import glob
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
#: The line of a TPU plane that holds one event per device operation.
OPS_LINE = "XLA Ops"


def load(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(tr: dict) -> list[tuple[int, dict]]:
    """(device id, plane) for every TPU, by id."""
    found = []
    for plane in tr["planes"]:
        m = _DEVICE.match(plane["name"])
        if m:
            found.append((int(m.group(1)), plane))
    return sorted(found, key=lambda p: p[0])


def ops(plane: dict) -> list:
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return line["events"]
    return []


def search_spans(tr: dict, span: str) -> dict[int, tuple[float, float]]:
    """Call index -> (start_ns, end_ns) of the harness's spans ``<span><i>``."""
    spans = {}
    for plane in tr["planes"]:
        if _DEVICE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(span):
                    spans[int(name[len(span):])] = (start, start + dur)
    return spans


def label(name: str) -> str:
    """A device op's short name: its HLO instruction, its kind and, for a
    custom call, its target (``%step.1 custom-call tpu_custom_call``)."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    kind = re.search(r"\s([a-z][a-z0-9\-]*)\(", rhs)
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    return " ".join([lhs] + ([kind.group(1)] if kind else []) + ([target.group(1)] if target else []))


def clip(events, t0: float, t1: float):
    """Events cut to ``[t0, t1]``; those wholly outside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Device:
    id: int
    busy_ns: float
    ops: list  # (name, start, end), clipped to the window
    gaps: list  # idle (start, end) inside the window


@dataclasses.dataclass
class Reduced:
    window_ns: float
    devices: list[Device]
    host_search: list[tuple[float, float]]  # the driver's call spans, clipped
    idle_labels: tuple[str, str]  # idle inside a call, and outside every call

    def events(self, dev: Device, match) -> list:
        return [o for o in dev.ops if match(o[0])]

    def idle_share(self, dev: Device) -> float:
        return 1.0 - dev.busy_ns / self.window_ns

    def breakdown(self, top: int = 10) -> dict:
        """Device ops by time (mean over devices) and idle time by what the
        host was doing, in seconds."""
        n = len(self.devices)
        by_op: dict[str, float] = {}
        for dev in self.devices:
            for name, s, e in dev.ops:
                key = label(name)
                by_op[key] = by_op.get(key, 0.0) + (e - s) / n
        by_host: dict[str, float] = {}
        for dev in self.devices:
            for s, e in dev.gaps:
                mid = (s + e) / 2
                inside = any(a <= mid <= b for a, b in self.host_search)
                key = self.idle_labels[0] if inside else self.idle_labels[1]
                by_host[key] = by_host.get(key, 0.0) + (e - s) / n
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {
            "device_ops": [[k, v / 1e9] for k, v in rank(by_op)],
            "idle_gaps": [[k, v / 1e9] for k, v in rank(by_host)],
        }


def reduce(tr: dict, span: str, first_call: int, last_call: int,
           idle_labels: tuple[str, str]) -> Reduced | None:
    """Clip every TPU's ops to the window that runs from the end of call
    ``first_call`` to the end of call ``last_call`` (spans ``<span><i>``).
    None where the trace holds no TPU or lacks either edge."""
    spans = search_spans(tr, span)
    planes = device_planes(tr)
    if not planes or first_call not in spans or last_call not in spans:
        return None
    t0, t1 = spans[first_call][1], spans[last_call][1]
    devices = []
    for dev_id, plane in planes:
        clipped = clip(ops(plane), t0, t1)
        busy = union((s, e) for _, s, e in clipped)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        devices.append(Device(dev_id, sum(e - s for s, e in busy), clipped, gaps))
    host = [(max(a, t0), min(b, t1)) for a, b in spans.values() if b > t0 and a < t1]
    return Reduced(t1 - t0, devices, host, idle_labels)
