"""Adding a cell, a driver or a per-layer metric is adding files: the
harness finds a new traffic file, a new driver, a new configuration, a
new workload entry and a new reader by name, and no file of the harness
changes."""

import json
import pathlib
import shutil
import textwrap
import types

import pytest

from perfbench import cell, run, view

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_new_workload_and_metric_are_found_by_name(tmp_path):
    root = _checkout(tmp_path)
    before = {p.relative_to(root) : p.read_bytes() for p in (root / "perfbench").rglob("*.py")}
    (root / "perfbench/traffic/d24.json").write_text(json.dumps(
        {"driver": "mine", "difficulty": 24, "warmup_blocks": 3, "first_hit_sample": 4,
         "rehearsal": {"difficulty": 9, "warmup_blocks": 2, "first_hit_sample": 100}}))
    (root / "perfbench/metrics/blocks_seen.py").write_text(
        "def read(run):\n    return float(run.blocks)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "solo-d24", "config": "solo-v5e-1", "traffic": "d24",
                               "chips": 1, "why": "a new cell"})
    next(m for m in bench["end_to_end"] if m["name"] == "hashes_per_s")["workloads"].append("solo-d24")
    bench["per_layer"].append({"name": "blocks_seen", "unit": "blocks", "better": "higher",
                               "source": "program_counter", "layer": "node",
                               "moves": "hashes_per_s", "workloads": ["solo-d24"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = cell.load("solo-d24", root)
    assert c.traffic["difficulty"] == 24 and c.config["node"]["backend"] == "tpu"
    assert [m["name"] for m in c.per_layer] == ["blocks_seen"]
    assert {m["name"] for m in c.end_to_end} == {"hashes_per_s", "setup_s"}
    run = types.SimpleNamespace(blocks=7, hashes=70, window_s=2.0, setup_s=1.5)
    assert cell.read_metrics(c, c.per_layer, run) == {"blocks_seen": {"value": 7.0, "unit": "blocks"}}
    assert cell.read_metrics(c, c.end_to_end, run) == {
        "hashes_per_s": {"value": 35.0, "unit": "H/s"}, "setup_s": {"value": 1.5, "unit": "s"}}
    after = {p.relative_to(root): p.read_bytes() for p in (root / "perfbench").rglob("*.py")
             if p.name != "blocks_seen.py"}
    assert after == before


#: A driver with no device and no program: it echoes the traffic's word.
ECHO = textwrap.dedent('''
    import dataclasses
    import time
    import types

    SPAN = "echo."
    IDLE = ("in an echo", "between echoes")


    @dataclasses.dataclass
    class Window:
        echoes: list
        setup_s: float
        first: int = 0
        last: int = 1
        compiles_in_window: int = 0
        phases: dict = dataclasses.field(default_factory=dict)
        stalls: list = dataclasses.field(default_factory=list)


    def drive(cell, traffic, seconds, *, rehearse, t_process, trace_dir, patch, meter):
        win = Window([traffic["word"]] * traffic["count"], time.perf_counter() - t_process)
        if patch is not None:
            patch(win)
        return win


    def compare(win, seed, devices, traffic):
        wrong = sum(e != traffic["word"] for e in win.echoes)
        return {"echo_diff": {"value": wrong, "limit": 0}}, len(win.echoes), wrong


    def view(win, cell, reduced, peak):
        return types.SimpleNamespace(setup_s=win.setup_s, echoes=len(win.echoes), window_s=2.0,
                                     trace=reduced)


    def facts(win):
        return {"echoes": len(win.echoes)}


    def control(win):
        win.echoes[-1] = win.echoes[-1].upper()
''')


def _add_echo_cell(root, traffic: dict) -> None:
    (root / "perfbench/drivers/echo.py").write_text(ECHO)
    (root / "perfbench/configs/echo-1.json").write_text(json.dumps({"name": "echo-1"}))
    (root / "perfbench/traffic/echo.json").write_text(json.dumps(traffic))
    (root / "perfbench/metrics/echoes_per_s.py").write_text(
        "def read(run):\n    return run.echoes / run.window_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "echo-1", "source": "a test", "reduced": [],
                             "file": "perfbench/configs/echo-1.json", "why": "a test"})
    bench["workloads"].append({"name": "echo-cell", "config": "echo-1", "traffic": "echo",
                               "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "echoes_per_s", "unit": "echoes/s", "better": "higher",
                                "bound": 0.01, "source": "host_clock", "workloads": ["echo-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_driver_config_traffic_and_metric_are_found_by_name(tmp_path, capsys, monkeypatch):
    root = _checkout(tmp_path)
    before = {p.relative_to(root): p.read_bytes() for p in (root / "perfbench").rglob("*.py")}
    _add_echo_cell(root, {"driver": "echo", "word": "hi", "count": 5,
                          "rehearsal": {"count": 3}})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(root / ".jax_cache"))

    c = cell.load("echo-cell", root)
    assert c.driver == "echo" and c.config == {"name": "echo-1"}
    assert {m["name"] for m in c.end_to_end} == {"echoes_per_s", "setup_s"}
    drv = cell.driver(c)
    win = drv.drive(c, c.traffic, 1.0, rehearse=False, t_process=0.0, trace_dir=None,
                    patch=None, meter=None)
    metrics = cell.read_metrics(c, c.end_to_end, drv.view(win, c, None, {}))
    assert metrics["echoes_per_s"] == {"value": 2.5, "unit": "echoes/s"}
    # The whole run goes through the new driver, and so does the control.
    for patch, correct in ((None, True), (drv.control, False)):
        assert run.main(["--workload", "echo-cell", "--seed", "5", "--seconds", "1",
                         "--rehearse"], patch=patch, root=root) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["correct"] is correct and out["attempted"] == 3
        assert out["facts"]["echoes"] == 3 and list(out)[-1] == "checks"
    after = {p.relative_to(root): p.read_bytes() for p in (root / "perfbench").rglob("*.py")
             if p.name not in ("echo.py", "echoes_per_s.py")}
    assert after == before


@pytest.mark.parametrize("traffic", [{"word": "hi", "count": 5}, {"driver": "none", "word": "hi"}],
                         ids=["no driver key", "no such driver"])
def test_a_traffic_without_its_driver_is_refused_by_name(tmp_path, capsys, traffic):
    root = _checkout(tmp_path)
    _add_echo_cell(root, traffic)
    with pytest.raises(KeyError, match="driver"):
        cell.load("echo-cell", root)
    assert run.main(["--workload", "echo-cell", "--seed", "5", "--seconds", "1", "--rehearse"],
                    root=root) == 2
    captured = capsys.readouterr()
    assert captured.out == "" and "driver" in captured.err and "'echo'" in captured.err


def test_a_reader_with_nothing_to_read_is_left_out(tmp_path):
    c = cell.load("solo-d20", _checkout(tmp_path))
    run = types.SimpleNamespace(blocks=0, gaps_ms=[], trace=None, setup_s=3.0)
    assert cell.read_metrics(c, c.per_layer, run) == {}
    assert cell.read_metrics(c, c.end_to_end, run) == {"setup_s": {"value": 3.0, "unit": "s"}}


def test_every_metric_of_every_cell_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = cell.load(w["name"])
        for m in c.end_to_end + c.per_layer:
            assert callable(cell.reader(c.metrics_dir, m["name"]))


def _header(timestamp, nonce):
    return types.SimpleNamespace(timestamp=timestamp, nonce=nonce)


def test_useful_hashes_come_from_the_headers():
    seal = types.SimpleNamespace(template=_header(1000, 0), sealed=_header(1000, 41),
                                 start_nonce=0)
    assert view.useful_hashes(seal) == 42
    seal.start_nonce = 40
    assert view.useful_hashes(seal) == 2
    # Two timestamp rolls: the whole nonce space twice, then from 0.
    seal = types.SimpleNamespace(template=_header(1000, 0), sealed=_header(1002, 9),
                                 start_nonce=0)
    assert view.useful_hashes(seal) == 2 * 2**32 + 10
    seal.sealed = None
    assert view.useful_hashes(seal) == 0
