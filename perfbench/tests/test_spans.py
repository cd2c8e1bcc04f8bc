"""The program's spans read beside the device trace (``perfbench/spans.py``):
the loader on a CPU capture, and the idle split, the launch join and the
per-block readings on a small hand-made trace."""

import json
import pathlib

import pytest

from perfbench import spans, trace
from perfbench.drivers import mine

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
KERNEL = '%sha256d_search.1 = s32[1]{0:T(128)} custom-call(u32[8]{0} %a), custom_call_target="tpu_custom_call"'


def _plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def _hand_made():
    """A window from t=100 to t=1100 (the ends of search calls 3 and 5),
    two devices, and the program's spans of two blocks.

    Device 0 runs kernels 50..400, 600..1000 and 1050..1300; device 1
    runs 50..380, 600..900 and 1050..1200.  The first was launched before
    the trace began (launch 6, never read back); launch 7 is dispatched
    at 130 and read back at 580..1010; launch 8, dispatched at 570, is
    abandoned.  Block 10 is searched over 120..520 and sealed over
    525..555 (its store 530..540); block 11 is searched over 560..1100.
    """
    tr = {"planes": [
        _plane("/device:TPU:0", "XLA Ops", [[KERNEL, 50, 350], [KERNEL, 600, 400],
                                            [KERNEL, 1050, 250]]),
        _plane("/device:TPU:1", "XLA Ops", [[KERNEL, 50, 330], [KERNEL, 600, 300],
                                            [KERNEL, 1050, 150]]),
        _plane("/host:CPU", "python3", [["perfbench.search.3", 0, 100],
                                        ["perfbench.search.4", 120, 400],
                                        ["perfbench.search.5", 560, 540]]),
    ]}
    data = {"spans": [
        ["p1.mine.search", 120, 400, {"height": 10}],
        ["p1.search.prepare", 125, 5, {}],
        ["p1.search.dispatch", 130, 10, {"launch": 7, "nonces": 4096}],
        ["p1.mine.post_seal", 525, 30, {"height": 10}],
        ["p1.stage.store", 530, 10, {"height": 10}],
        ["p1.mine.search", 560, 540, {"height": 11}],
        ["p1.search.dispatch", 570, 10, {"launch": 8, "nonces": 4096}],
        ["p1.search.readback", 580, 430, {"launch": 7}],
    ]}
    return tr, data


def test_idle_splits_exactly_among_the_innermost_spans():
    tr, data = _hand_made()
    r = trace.reduce(tr, mine.SPAN, 3, 5, mine.IDLE)
    rows = dict(spans.idle_by_span(r, spans.spans(data), 100, 1100))
    # Device 0 idles 400..600 and 1000..1050, device 1 380..600 and
    # 900..1050; rows are the mean over the two devices.
    want = {"p1.mine.search": 180, "p1.search.readback": 80, "p1.mine.post_seal": 20,
            "p1.stage.store": 10, "p1.search.dispatch": 10, spans.NO_SPAN: 10}
    assert rows == pytest.approx({k: v / 1e9 for k, v in want.items()})
    idle = sum(sum(e - s for s, e in d.gaps) for d in r.devices) / 2
    assert sum(rows.values()) == pytest.approx(idle / 1e9, rel=1e-12)


def test_segments_tile_the_window():
    _, data = _hand_made()
    pieces = spans.segments(spans.spans(data), 100, 1100)
    assert pieces[0][0] == 100 and pieces[-1][1] == 1100
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    assert (530, 540, "p1.stage.store") in pieces
    assert (520, 525, spans.NO_SPAN) in pieces


def test_join_is_first_in_first_out_with_a_launch_from_before_the_trace():
    tr, data = _hand_made()
    found = spans.spans(data)
    planes = trace.device_planes(tr)
    j0 = spans.join(spans.kernel_ops(planes[0][1]), found)
    assert [op[2] for op in j0.ops] == [6, 7, 8]
    assert [j0.abandoned(op[2]) for op in j0.ops] == [True, False, True]
    assert j0.unmatched == 0
    # Without any dispatch span there is nothing to match.
    none = spans.join(spans.kernel_ops(planes[0][1]), [])
    assert [op[2] for op in none.ops] == [None] * 3 and none.unmatched == 3


def test_abandoned_share_and_per_block_readings():
    tr, data = _hand_made()
    out = spans.report(tr, trace.reduce(tr, mine.SPAN, 3, 5, mine.IDLE), data, 3, 5, blocks=2)
    # Window kernel time: device 0 300 + 400 + 50, device 1 280 + 300 + 50;
    # abandoned: the first and last of each.
    assert out["abandoned_kernel_share"] == pytest.approx(100 * (350 + 330) / (750 + 630))
    assert out["post_seal_ms_per_block"] == pytest.approx(30 / 1e6 / 2)
    assert out["search_host_ms_per_block"] == pytest.approx((5 + 10 + 10) / 1e6 / 2)
    assert out["window_kernel_ops_device0"] == 3
    assert out["window_kernel_ops_abandoned_device0"] == 2
    assert out["window_kernel_ops_unmatched"] == 0
    assert out["window_dispatches"] == 2
    assert out["idle_s"] == pytest.approx(310 / 1e9)
    assert sum(v for _, v in out["idle_by_span"]) == pytest.approx(out["idle_s"])


def test_loader_reads_program_spans_and_their_stats(tmp_path):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("p1.mine.search", height=3):
            with jax.profiler.TraceAnnotation("p1.search.dispatch", launch=4, nonces=512):
                jax.numpy.arange(4).block_until_ready()
        with jax.profiler.TraceAnnotation("perfbench.search.0"):
            pass
    finally:
        jax.profiler.stop_trace()
    data = spans.load(str(tmp_path))
    found = spans.spans(data)
    assert [(sp.name, sp.stats) for sp in found] == [
        ("p1.mine.search", {"height": 3}),
        ("p1.search.dispatch", {"launch": 4, "nonces": 512}),
    ]
    outer, inner = found
    assert outer.start <= inner.start and inner.end <= outer.end
    json.dumps(data)  # plain data


@pytest.mark.parametrize("name, busy_ns, ops, idle_gaps", [
    ("solo-d20", 20861877.0, [["%step.1 custom-call tpu_custom_call", 0.020848117],
                              ["%copy.1 copy", 1.3149e-05]],
     [["host in search_nonce: dispatch and readback", 0.034950942],
      ["host outside search_nonce: node assemble, post-seal, push", 0.034658976]]),
    ("solo-d28", 2196937382.0, [["%step.1 custom-call tpu_custom_call", 2.19692366],
                                ["%copy.1 copy", 1.3052e-05]],
     [["host in search_nonce: dispatch and readback", 0.000142999]]),
])
def test_recorded_traces_reduce_as_before(name, busy_ns, ops, idle_gaps):
    """The traces recorded on the chip before the program had spans give
    the same numbers, and all their idle time is ``no program span``."""
    tr = json.loads((FIXTURES / f"trace_{name}.json").read_text())
    r = trace.reduce(tr, mine.SPAN, tr["first"], tr["last"], mine.IDLE)
    assert r.devices[0].busy_ns == busy_ns
    bd = r.breakdown()
    assert bd["device_ops"][:2] == ops and bd["idle_gaps"] == idle_gaps
    edges = trace.search_spans(tr, mine.SPAN)
    t0, t1 = edges[tr["first"]][1], edges[tr["last"]][1]
    rows = spans.idle_by_span(r, [], t0, t1)
    assert [k for k, _ in rows] == [spans.NO_SPAN]
    assert rows[0][1] == pytest.approx(sum(v for _, v in idle_gaps), rel=1e-9)
