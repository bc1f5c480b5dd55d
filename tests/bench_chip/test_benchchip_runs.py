"""Whole runs of the chip benchmark at PF(7) on the CPU: the harness's
answer functions and checks pass on the program as it is, the controls
fail their checks, and planted faults in the timed path make `correct`
come out false.  A run that finds no TPU exits non-zero with no result."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _chipbench as cb

harness = cb.harness
import run  # noqa: E402  (benchmarks/chip/run.py)

CELLS = {w["name"]: (w["config"], w["traffic"])
         for w in harness.benchmark_spec()["workloads"]}


def one_run(cell, trace=0, seed=20_000_000_001):
    cfg_name, mix_name = CELLS[cell]
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "0.1", "--trace", str(trace)], require_tpu=False,
                    config=cb.tiny_config(cfg_name),
                    traffic=cb.tiny_traffic(mix_name))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_tiny_run_is_correct(cell, capsys):
    out = one_run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line) == out
    assert list(out)[-1] == "checks"
    e2e = {m["name"] for m in harness.cell_metrics(
        harness.benchmark_spec(), cell, "end_to_end")}
    assert set(out["metrics"]) == e2e
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_run_reports_per_layer_metrics():
    out = one_run("pf31_ugal.tail", trace=1)
    assert out["correct"]
    assert {"packet_prep_s", "packet_cycle_ms"} <= set(out["metrics"])
    assert "window_s" in out["device"] and "busy_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


# PF(5) for the min cell: at p = 3 endpoints its saturation 1 / (3 x most
# flows on a link) is never on a bisection grid, as 1 / (40 x ...) is not
# at PF(79); at PF(7) (p = 4) it can be
@pytest.mark.parametrize("cell,q", [("pf31_ugal.sat", 7), ("pf79_min.sat", 5),
                                    ("pf31_ugal.tail", 7)])
def test_control_fails_the_check(cell, q):
    cfg_name, mix_name = CELLS[cell]
    cfg, mix = cb.tiny_config(cfg_name, q), cb.tiny_traffic(mix_name)
    kind = harness.load_module("answers", mix["answer"])
    limits = harness.load_limits(cell)
    inputs = harness.draw_inputs(cfg["N"], 5)
    got = kind.answer(cfg, mix, inputs, harness.Spans())
    assert harness.checks_ok(kind.check(cfg, mix, inputs, got, limits))
    assert not harness.checks_ok(kind.control(cfg, mix, inputs, got,
                                              limits))


def _alter_value(monkeypatch):
    from repro.simulation import fluid

    real = fluid.saturation_throughput

    def altered(fp, **kw):
        res = real(fp, **kw)
        res.value = min(1.0, res.value + 0.25)
        return res
    monkeypatch.setattr(fluid, "saturation_throughput", altered)


def _no_steps(monkeypatch):
    # every Frank-Wolfe step returns its state unchanged: the solve keeps
    # the starting split (everything on the minimal path)
    from repro.simulation import fluid

    real = fluid.saturation_throughput
    monkeypatch.setattr(fluid, "saturation_throughput",
                        lambda fp, **kw: real(fp, **dict(kw, cert_iters=0)))


def _half_the_flows(monkeypatch):
    from repro.simulation import paths, traffic

    real = paths.build_flow_paths

    def half(rt, pat, mode, **kw):
        h = pat.num_flows // 2
        sub = traffic.TrafficPattern(pat.name, pat.src[:h], pat.dst[:h],
                                     pat.demand[:h],
                                     pat.endpoints_per_router)
        return real(rt, sub, mode, **kw)
    monkeypatch.setattr(paths, "build_flow_paths", half)


def _late_packet(monkeypatch):
    from repro.simulation import packet

    real = packet.simulate_packets

    def altered(wl, *a, **kw):
        res = real(wl, *a, **kw)
        i = int(np.flatnonzero(res.delivered)[0])
        res.deliver_t = res.deliver_t.copy()
        res.deliver_t[i] += 1
        return res
    monkeypatch.setattr(packet, "simulate_packets", altered)


def _other_phases(monkeypatch):
    # the workload draws its arrival phases from another seed than the one
    # the benchmark hands it
    from repro.simulation import packet

    real = packet.make_workload
    monkeypatch.setattr(packet, "make_workload",
                        lambda fp, *a, seed=0, **kw: real(fp, *a,
                                                          seed=seed + 1, **kw))


def _frozen_cycles(monkeypatch):
    # every packet cycle returns its state unchanged: nothing moves
    from repro.simulation import packet

    real = packet.simulate_packets

    def frozen(wl, *a, **kw):
        res = real(wl, *a, **kw)
        res.delivered = np.zeros_like(res.delivered)
        return res
    monkeypatch.setattr(packet, "simulate_packets", frozen)


FAULTS = [
    ("pf31_ugal.sat", _alter_value), ("pf31_ugal.sat", _no_steps),
    ("pf31_ugal.sat", _half_the_flows), ("pf79_min.sat", _alter_value),
    ("pf79_min.sat", _half_the_flows), ("pf31_ugal.tail", _late_packet),
    ("pf31_ugal.tail", _frozen_cycles), ("pf31_ugal.tail", _half_the_flows),
    ("pf31_ugal.tail", _other_phases),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_fault_in_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    assert not one_run(cell)["correct"]


def test_knee_sweep_accepts_what_it_can_carry():
    import knee

    low, high = knee.main(
        ["--workload", "pf31_ugal.tail", "--seed", "3", "--loads", "0.05",
         "3.0"], require_tpu=False, config=cb.tiny_config("pf31_ugal"),
        traffic=cb.tiny_traffic("tail"))
    assert low["accepted"] == pytest.approx(0.05, rel=0.2)
    assert low["source_backlog"] == 0
    assert high["accepted"] < 3.0 and high["source_backlog"] > 0


def test_checkout_without_the_program_is_an_error(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit):
        harness.add_program()


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(cb.CHIP, "run.py"), "--workload",
         "pf31_ugal.sat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
