"""Time inside the program's ``p1.mempool.select`` spans (the pool's
selection of each template's transfers) over the window, per window
block, in ms; None where the trace holds no such span."""


def read(run):
    return (run.spans_ms_per_block or {}).get("p1.mempool.select")
