"""Time inside the program's ``p1.mine.post_seal`` spans (seal to the end
of the block's connect, store, eviction and push) over the window, per
window block, in ms; None where the trace holds no such span."""


def read(run):
    return (run.spans_ms_per_block or {}).get("p1.mine.post_seal")
