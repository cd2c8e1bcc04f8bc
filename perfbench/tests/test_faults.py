"""With the timed path broken underneath, ``correct`` comes out false.

Each fault a cell can have, planted in the program below the harness, in
a rehearsal run (CPU, rehearsal sizes, the Pallas kernel in the
interpreter); and the control that each driver supplies to
``perfbench/control.py``.
"""

import json

import pytest

from perfbench import run
from perfbench.drivers import mine, replay


def _run(capsys, cell, patch, seconds="2"):
    rc = run.main(["--workload", cell, "--seed", "3100000003", "--seconds", seconds,
                   "--rehearse"], patch=patch)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _wrap_search(node, mp, change):
    """Patch the backend's ``search`` (the host loop over device steps);
    ``mp`` undoes it after the test, since backends are shared."""
    backend = node.miner.backend
    search = backend.search

    def patched(prefix, start, count, difficulty):
        return change(search, prefix, start, count, difficulty)

    mp.setattr(backend, "search", patched)


def answer_altered(node, mp):
    """The hit's nonce is off by one where the host loop produces it."""
    from p1_tpu.hashx.backend import SearchResult

    def change(search, prefix, start, count, d):
        res = search(prefix, start, count, d)
        if res.nonce is None:
            return res
        return SearchResult(res.nonce + 1, res.hashes_done + 1)

    _wrap_search(node, mp, change)


def half_left_out(node, mp):
    """Odd nonces are never hashed: a hit there is not seen."""
    from p1_tpu.hashx.backend import SearchResult

    def change(search, prefix, start, count, d):
        done = 0
        while True:
            res = search(prefix, start, count, d)
            done += res.hashes_done
            if res.nonce is None or res.nonce % 2 == 0:
                return SearchResult(res.nonce, done)
            count -= res.nonce + 1 - start
            start = res.nonce + 1
            if count <= 0:
                return SearchResult(None, done)

    _wrap_search(node, mp, change)


def unchanged(node, mp):
    """The search hands back the template as it came in."""
    search = node.miner.search_nonce

    def search_nonce(header, abort=None, start_nonce=0):
        return header if search(header, abort, start_nonce) is not None else None

    node.miner.search_nonce = search_nonce


def second_hit(node, mp):
    mine.control(node)


@pytest.mark.parametrize("cell", ["solo-d28", "pod-d28"])
@pytest.mark.parametrize("fault", [answer_altered, half_left_out, unchanged, second_hit],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(capsys, monkeypatch, cell, fault):
    out = _run(capsys, cell, lambda node: fault(node, monkeypatch))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_exchange_between_chips_left_out(capsys, monkeypatch):
    """The pod's first-hit reduction is dropped: each chip keeps its own
    answer and chip 0's is read."""
    from jax import lax

    from p1_tpu.hashx import sharded

    sharded.jit_sharded_step.cache_clear()
    monkeypatch.setattr(lax, "pmin", lambda x, axis_name: x)
    try:
        out = _run(capsys, "pod-d28", None)
    finally:
        sharded.jit_sharded_step.cache_clear()
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["not_first"]["value"] > 0, out


def test_sound_run_in_process_is_correct(capsys):
    assert _run(capsys, "solo-d28", None)["correct"] is True


def _wrap_scan(mp, change):
    """Patch the program's chain scan: ``change(step, words3, target,
    difficulty, segment)`` stands in for each call of the built step."""
    from p1_tpu.hashx import jax_sha256

    build = jax_sha256.jit_verify_chain_scan

    def patched(n_segments, segment, platform=None, unroll=None):
        step = build(n_segments, segment, platform, unroll)
        return lambda words3, target, d: change(step, words3, target, d, segment)

    mp.setattr(jax_sha256, "jit_verify_chain_scan", patched)


def scan_answer_altered(win, mp):
    """Each segment's first invalid index is off by one where the scan
    produces it."""
    import jax.numpy as jnp

    def change(step, words3, target, d, segment):
        idx = step(words3, target, d)
        return jnp.where(idx < segment, idx + 1, idx)

    _wrap_scan(mp, change)


def scan_half_left_out(win, mp):
    """The scan's second half of the segments reads clean, unverified."""
    import jax.numpy as jnp

    def change(step, words3, target, d, segment):
        idx = step(words3, target, d)
        keep = jnp.arange(idx.shape[0]) < idx.shape[0] // 2
        return jnp.where(keep, idx, segment)

    _wrap_scan(mp, change)


def replay_unchanged(win, mp):
    """Every replay hands back the first replay's verdict."""
    verify, first = win.verify, []

    def same(headers):
        if not first:
            first.append(verify(headers))
        return first[0]

    win.verify = same


def skip_last_segment(win, mp):
    replay.control(win, mp.setattr)


@pytest.mark.parametrize("fault", [scan_answer_altered, scan_half_left_out, replay_unchanged,
                                   skip_last_segment], ids=lambda f: f.__name__)
def test_replay_fault_is_not_correct(capsys, monkeypatch, fault):
    out = _run(capsys, "replay-mainnet", lambda win: fault(win, monkeypatch))
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["missed_corruption"]["value"] > 0, out
    assert out["failed"] > 0


def test_sound_replay_in_process_is_correct(capsys):
    out = _run(capsys, "replay-mainnet", None)
    assert out["correct"] is True and out["failed"] == 0, out["checks"]
