"""Driver ``mine``: the coinbase-only mining node in a closed loop.

The node and its window are ``perfbench/drive.py``'s, the comparison is
``perfbench/checks.py``'s against ``reference.FirstHitScan``, and the
readers get ``perfbench/view.py``'s ``View``.
"""

from __future__ import annotations

import asyncio

from perfbench import checks, reference
from perfbench import drive as node_drive
from perfbench import view as node_view

SPAN = node_drive.SEARCH_SPAN
IDLE = ("host in search_nonce: dispatch and readback",
        "host outside search_nonce: node assemble, post-seal, push")
#: The device reference scan's step and round unroll, by platform.
SCAN = {"tpu": (1 << 22, 64), "cpu": (1 << 12, 1)}


def drive(cell, traffic, seconds, *, rehearse, t_process, trace_dir, patch, meter):
    return asyncio.run(node_drive.drive(
        cell, traffic["chain"], seconds, difficulty=traffic["difficulty"],
        warmup_blocks=traffic["warmup_blocks"], rehearse=rehearse,
        t_process=t_process, trace_dir=trace_dir, patch=patch, meter=meter))


def compare(win, seed, devices, traffic):
    batch, unroll = SCAN[devices[0].platform]
    scan = reference.FirstHitScan(devices, batch, unroll)
    return checks.compare(win, seed, scan, traffic["first_hit_sample"])


def view(win, cell, reduced, peak):
    return node_view.View.of(win, cell, reduced, peak)


def hashes_done_gap(win) -> int:
    """The program's own count of the window's hashes less the harness's:
    0 while both count nonces up to each hit."""
    inside = win.seals[win.first + 1:win.last + 1] if win.blocks else []
    return sum(s.hashes_done for s in inside if s.sealed is not None) - sum(
        node_view.useful_hashes(s) for s in inside)


def facts(win) -> dict:
    return {
        "blocks": len(win.blocks),
        "first_nonces": [h.nonce for h in win.served[1:6]],
        "height": len(win.served) - 1,
        "batch": win.batch,
        "hashes_done_gap": hashes_done_gap(win),
        "push_coalesced": win.coalesced,
    }


def control(node) -> None:
    """The guarantee that the sealed nonce is the first hit, broken: each
    block is sealed with the header's second hit in nonce order.  A valid
    proof of work, so the node accepts it and every other layer is as
    usual, but not the first hit.  This is the shortcut a faster miner
    would be tempted by (a pod that takes any chip's hit, a kernel that
    returns any lane's), and only ``not_first`` can see it."""
    miner = node.miner
    search = miner.search_nonce

    def search_nonce(header, abort=None, start_nonce=0):
        first = search(header, abort, start_nonce)
        if first is None:
            return None
        return search(header, abort, first.nonce + 1)

    miner.search_nonce = search_nonce
