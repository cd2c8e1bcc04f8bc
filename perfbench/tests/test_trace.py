"""The reduction from trace to per-layer numbers, on a hand-made trace and
on short traces recorded on the chip (``fixtures/``)."""

import json
import pathlib
import types

import pytest

from perfbench import layers, trace
from perfbench.drivers import mine

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
KERNEL = '%step.1 = s32[1]{0:T(128)} custom-call(u32[8]{0} %a), custom_call_target="tpu_custom_call"'
COPY = "%copy.1 = u32[8]{0:T(128)} copy(u32[8]{0:T(128)} %args_0_.1)"
REDUCE = "%all-reduce.1 = u32[] all-reduce(u32[] %select.2), channel_id=1, to_apply=%region"


def _plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def _hand_made():
    """Two devices over a window from t=100 to t=1100 (the ends of search
    calls 3 and 5).  Device 0: kernel 50..400 (clipped to 100..400), copy
    350..450 (overlaps it), kernel 600..1000, all-reduce 1000..1010, and a
    kernel 1050..1300 (clipped to 1050..1100).  Device 1: one kernel
    200..700 and an all-reduce 700..720."""
    return {"planes": [
        _plane("/device:TPU:0", "XLA Ops", [[KERNEL, 50, 350], [COPY, 350, 100],
                                            [KERNEL, 600, 400], [REDUCE, 1000, 10],
                                            [KERNEL, 1050, 250]]),
        _plane("/device:TPU:1", "XLA Ops", [[KERNEL, 200, 500], [REDUCE, 700, 20]]),
        _plane("/host:CPU", "python3", [["perfbench.search.3", 0, 100],
                                        ["perfbench.search.4", 120, 400],
                                        ["perfbench.search.5", 560, 540]]),
    ]}


def test_busy_union_idle_and_gaps():
    r = trace.reduce(_hand_made(), mine.SPAN, 3, 5, mine.IDLE)
    assert r.window_ns == 1000
    d0, d1 = r.devices
    assert d0.busy_ns == (450 - 100) + (1010 - 600) + (1100 - 1050)
    assert d0.gaps == [(450, 600), (1010, 1050)]
    assert d1.busy_ns == 520 and d1.gaps == [(100, 200), (720, 1100)]
    assert r.idle_share(d0) == pytest.approx(0.19)
    assert len(r.events(d0, layers.is_kernel)) == 3
    assert len(r.events(d0, layers.is_collective)) == 1
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["%step.1 custom-call tpu_custom_call", (300 + 400 + 50 + 500) / 2 / 1e9]
    # Gaps whose middle falls in a search call are the host loop's; the
    # one from 450 to 600 falls between calls 4 (120..520) and 5.
    idle = dict(bd["idle_gaps"])
    assert idle["host in search_nonce: dispatch and readback"] == pytest.approx((40 + 100 + 380) / 2 / 1e9)
    assert idle["host outside search_nonce: node assemble, post-seal, push"] == pytest.approx(150 / 2 / 1e9)


def test_layer_readings_on_the_hand_made_trace():
    r = trace.reduce(_hand_made(), mine.SPAN, 3, 5, mine.IDLE)
    run = types.SimpleNamespace(trace=r, blocks=2, hashes=10**9, window_s=1e-6, chips=2,
                                ops_per_hash=4621, peak_ops_per_s=6e12)
    assert layers.launches_per_block(run) == 1.5
    kernel_s = (300 + 400 + 50 + 500) / 1e9
    assert layers.kernel_roofline(run) == pytest.approx(100 * 1e9 * 4621 / (kernel_s * 6e12))
    assert layers.collective_ms_per_step(run) == pytest.approx(30 / 2 / 3 / 1e6)
    assert layers.idle_share(run) == pytest.approx(100 * 0.48)


def test_no_device_or_no_window_edge_reads_nothing():
    tr = _hand_made()
    assert trace.reduce(tr, mine.SPAN, 3, 9, mine.IDLE) is None
    tr["planes"] = [p for p in tr["planes"] if not p["name"].startswith("/device")]
    assert trace.reduce(tr, mine.SPAN, 3, 5, mine.IDLE) is None
    run = types.SimpleNamespace(trace=None, blocks=3)
    assert layers.launches_per_block(run) is None
    assert layers.kernel_roofline(run) is None
    assert layers.idle_share(run) is None


def test_a_renamed_kernel_reads_nothing_and_says_so(capsys):
    tr = _hand_made()
    for plane in tr["planes"][:2]:
        for ev in plane["lines"][0]["events"]:
            ev[0] = ev[0].replace("tpu_custom_call", "renamed")
    run = types.SimpleNamespace(trace=trace.reduce(tr, mine.SPAN, 3, 5, mine.IDLE), blocks=2,
                                hashes=10**9, ops_per_hash=4621, peak_ops_per_s=6e12)
    assert layers.kernel_roofline(run) is None
    assert layers.launches_per_block(run) is None
    assert capsys.readouterr().err.count("but no") == 2


def _recorded(name):
    tr = json.loads((FIXTURES / f"trace_{name}.json").read_text())
    return trace.reduce(tr, mine.SPAN, tr["first"], tr["last"], mine.IDLE)


@pytest.mark.parametrize("name, launches, idle", [
    # 8 blocks at d20 (window 90.5 ms): about two kernel launches a block,
    # the device idle three quarters of the time.
    ("solo-d20", 17, 0.769410157),
    # 8 blocks at d28 (2.2 s): the pipeline keeps the device busy.
    ("solo-d28", 21, 0.0000650859),
])
def test_recorded_trace(name, launches, idle):
    r = _recorded(name)
    (dev,) = r.devices
    assert len(r.events(dev, layers.is_kernel)) == launches
    assert r.idle_share(dev) == pytest.approx(idle, abs=1e-9)
    assert dev.busy_ns + sum(e - s for s, e in dev.gaps) == pytest.approx(r.window_ns)
    assert all(e - s <= r.window_ns for _, s, e in dev.ops)
    assert r.breakdown()["device_ops"][0][0] == "%step.1 custom-call tpu_custom_call"
    assert not r.events(dev, layers.is_collective)


def test_recorded_replay_trace(capsys):
    """Three replays of the 870,000-header chain on the chip, the device
    ops kept where no other op holds them: the scan's ``while`` loop over
    ``u32[107,8192,20]`` and a few small ops."""
    from perfbench import cell
    from perfbench.drivers import replay

    tr = json.loads((FIXTURES / "trace_replay-mainnet.json").read_text())
    r = trace.reduce(tr, replay.SPAN, tr["first"], tr["last"], replay.IDLE)
    (dev,) = r.devices
    scan_ns = replay.scan_ns(r, 107, 8192)
    assert scan_ns == pytest.approx(40063977) and dev.busy_ns == pytest.approx(40066317)
    assert r.breakdown()["device_ops"][0][0] == "%while.248 while"
    assert [k for k, _ in r.breakdown()["idle_gaps"]] == [replay.IDLE[0]]
    run = replay.View(chips=1, setup_s=1.0, window_s=r.window_ns / 1e9, replays=3,
                      headers=3 * 870000, trace=r, scan_ns=scan_ns, peak_ops_per_s=5.98e12)
    read = {name: cell.reader(cell.HERE / "metrics", name)(run) for name in
            ("verify_scan_roofline", "host_ms_per_replay", "device_idle_share.replay")}
    assert read["verify_scan_roofline"] == pytest.approx(
        100 * 3 * 870000 * 6932 / (40063977e-9 * 5.98e12))
    assert 7 < read["verify_scan_roofline"] < 8
    assert read["host_ms_per_replay"] == pytest.approx((r.window_ns - 40063977) / 1e6 / 3)
    assert read["device_idle_share.replay"] == pytest.approx(100 * (1 - 40066317 / r.window_ns))
    # Another chain's shape is not this scan: nothing to read, and said so.
    assert replay.scan_ns(r, 106, 8192) is None
    assert "no chain scan" in capsys.readouterr().err
