"""`program_spans.py`, which reads a cell's time from the program's own
spans, scopes and counters: a whole run at PF(7) on the CPU, and its
readings on recorded inputs."""

import pytest

import _chipbench as cb

import program_spans  # noqa: E402  (benchmarks/chip/program_spans.py)


def test_run_reads_the_host_spans_of_the_min_cell(capsys):
    out = program_spans.main(
        ["--workload", "pf79_min.sat", "--seed", "4300000077", "--pairs",
         "2"], require_tpu=False, config=cb.tiny_config("pf79_min"),
        traffic=cb.tiny_traffic("sat"))
    assert capsys.readouterr().out.strip().splitlines()[-1].startswith("{")
    got = out["readings"]
    assert got["paths_sweep_s"] > 0 and got["paths_walk_s"] > 0
    # min routing: the diameter sweep and one column sweep, per answer
    assert got["routing_retraces"] == 2
    assert set(out["counters"]) == {
        "blockwise.retrace[repro.core.routing._bfs_device_fn.fn]",
        "blockwise.retrace[repro.core.routing._dest_device_fn.fn]"}
    spans = out["spans"]
    assert (got["paths_sweep_s"] + got["paths_walk_s"]
            <= spans["harness:paths"]["total_s"])
    assert spans["routing.diameter"]["total_s"] <= \
        spans["harness:routing"]["total_s"]
    assert not any(n.startswith("blockwise.")
                   for n, _ in out["layer_idle_gaps"])
    # no device ops on a CPU: the device readings stay empty
    assert got["fw_loads_ms"] is None and got["packet_arbitrate_ms"] is None
    assert len(out["off_s"]) == len(out["on_s"]) == 2 and out["overhead"] > 0


@pytest.mark.parametrize("answer,scope,per,want", [
    ("sat", "fluid.loads", {"iters": 5000}, 2.0),
    ("tail", "packet.arbitrate", {}, 25.0),
    ("sat", "packet.arbitrate", {"iters": 5000}, None)])
def test_device_readings_per_step_and_per_cycle(answer, scope, per, want):
    mix = {"answer": answer, "params": {"cycles": 400}}
    got = program_spans.readings({}, {}, {scope: 10.0}, per, mix)
    name = "fw_loads_ms" if scope == "fluid.loads" else "packet_arbitrate_ms"
    assert got[name] == want


def test_host_readings_per_answer():
    events = [
        {"name": "paths.sweep", "ph": "X", "ts": 0.0, "dur": 3e6},
        {"name": "paths.walk", "ph": "X", "ts": 3e6, "dur": 1e6},
        {"name": "blockwise.retrace", "ph": "C", "ts": 1.0,
         "args": {"value": 1, "fn": "f"}},
        {"name": "blockwise.retrace", "ph": "C", "ts": 2.0,
         "args": {"value": 1, "fn": "g"}},
    ]
    spans, counters = program_spans.per_answer(events, 2)
    got = program_spans.readings(spans, counters, {}, {},
                                 {"answer": "sat", "params": {}})
    assert got["paths_sweep_s"] == 1.5 and got["paths_walk_s"] == 0.5
    assert got["routing_retraces"] == 1.0
    assert counters == {"blockwise.retrace[f]": 0.5,
                        "blockwise.retrace[g]": 0.5}
