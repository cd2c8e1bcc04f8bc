"""Spec operations of the window's headers (``opcount.header_verify_ops``)
over the chain scan's device time in the window, every device, times one
chip's peak, in %."""


def read(run):
    if run.scan_ns is None or not run.replays:
        return None
    return 100.0 * run.headers * run.ops_per_header / (run.scan_ns / 1e9 * run.peak_ops_per_s)
