"""Window time outside the chain scan's device ops, a replay, in ms: the
host's pack of the headers, the upload, dispatch and readback, and the
harness's loop."""


def read(run):
    if run.scan_ns is None or not run.replays:
        return None
    scan_ns = run.scan_ns / len(run.trace.devices)
    return (run.trace.window_ns - scan_ns) / 1e6 / run.replays
