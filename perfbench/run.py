"""The benchmark's one command.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of this machine and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, last, ``checks``
(each number the correctness comparison read, beside its limit, also the
last lines of standard error).

It refuses, printing no result, unless JAX's first device is a TPU and
there are as many as the cell asks for.  ``--rehearse`` runs the same
path on the CPU at the sizes the configuration and traffic files give
under ``rehearsal``, with the Pallas kernel in the interpreter; it prints
no metric, since a CPU run says nothing of the chip's speed.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, Pallas interpreter; no metrics")
    return ap.parse_args(argv)


def _refuse(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None, patch=None, root: pathlib.Path = ROOT) -> int:
    """``patch``, for tests and the control only, breaks the timed path
    underneath before the run starts (what it is handed is the driver's:
    ``drivers/__init__.py``)."""
    args = parse(argv)
    from perfbench import cell as cells

    try:
        cell = cells.load(args.workload, root)
    except (KeyError, FileNotFoundError) as exc:
        return _refuse(f"no such cell: {exc}")
    # The persistent compile cache lives in the checkout, at one fixed
    # path, whatever the machine sets; every compile is kept (the kernel's
    # take under JAX's default 1 s threshold).
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1 and "device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                       f" --xla_force_host_platform_device_count={cell.chips}")
    try:
        import p1_tpu  # noqa: F401
    except ImportError as exc:
        return _refuse(f"the program is not in this checkout: {exc}")
    drv = cells.driver(cell)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        return _refuse(f"JAX found no devices: {exc}")
    t_devices = time.perf_counter() - T_PROCESS
    platform, kind = devices[0].platform, devices[0].device_kind
    want = "cpu" if args.rehearse else "tpu"
    if platform != want or len(devices) < cell.chips:
        return _refuse(f"cell {cell.name} needs {cell.chips} {want} device(s); "
                       f"JAX found {len(devices)} {platform!r} ({kind})")
    peaks = json.loads(PEAKS.read_text())
    if not args.rehearse and kind not in peaks:
        return _refuse(f"no peak for device kind {kind!r} in {PEAKS.name}")
    used = devices[:cell.chips]

    from perfbench import trace
    from perfbench.compile_meter import CompileMeter

    traffic = dict(cell.traffic, **(cell.traffic["rehearsal"] if args.rehearse else {}))
    meter = CompileMeter()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if args.trace else None
    try:
        win = drv.drive(cell, traffic, args.seconds, rehearse=args.rehearse,
                        t_process=T_PROCESS, trace_dir=trace_dir, patch=patch, meter=meter)
        stats = [d.memory_stats() or {} for d in used]
        memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        t_ref = time.perf_counter()
        found, attempted, failed = drv.compare(win, args.seed, used, traffic)
        reference_s = time.perf_counter() - t_ref
        reduced = None
        if trace_dir is not None and win.first is not None and win.last is not None:
            reduced = trace.reduce(trace.load(trace_dir), drv.SPAN, win.first, win.last,
                                   drv.IDLE)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    correct = attempted > 0 and all(c["value"] <= c["limit"] for c in found.values())
    device = {"platform": platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    else:
        run = drv.view(win, cell, reduced, peaks[kind])
        entries = cell.per_layer if args.trace else cell.end_to_end
        result["metrics"] = cells.read_metrics(cell, entries, run)
        if args.trace and reduced is not None:
            device["busy_s"] = sum(d.busy_ns for d in reduced.devices) / len(reduced.devices) / 1e9
            device["window_s"] = reduced.window_ns / 1e9
            result["breakdown"] = reduced.breakdown()
    result["facts"] = {
        **drv.facts(win),
        "setup_phases": {"devices": t_devices, **win.phases},
        "compiles_in_window": win.compiles_in_window,
        "compiles": meter.compiles,
        "compile_s": meter.compile_s,
        "cache_hits": meter.cache_hits,
        "stalled": win.stalls,
        "reference_s": reference_s,
        "run_s": time.perf_counter() - T_PROCESS,
    }
    result["checks"] = found
    print(json.dumps(result["facts"]), file=sys.stderr)
    for name, c in found.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
