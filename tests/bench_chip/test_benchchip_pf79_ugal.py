"""The `pf79_ugal.sat` cell at PF(7) on the CPU (`tiny_config`): a whole
run is correct, the control its limits file names fails the check, and
faults planted in the timed path make `correct` come out false."""

import pytest

import _chipbench as cb

harness = cb.harness
import run  # noqa: E402  (benchmarks/chip/run.py)

CELL = "pf79_ugal.sat"
ENTRY = harness.workload_entry(harness.benchmark_spec(), CELL)


def one_run(seed=4_300_000_079):
    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.1", "--trace", "0"], require_tpu=False,
                    config=cb.tiny_config(ENTRY["config"]),
                    traffic=cb.tiny_traffic(ENTRY["traffic"]))


def test_tiny_run_is_correct():
    out = one_run()
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"paths_bad", "sat_off", "bracket_off"}
    assert set(out["metrics"]) == {"sat_answer_s.min", "setup_s"}


def test_named_control_fails_the_check():
    cfg = cb.tiny_config(ENTRY["config"])
    mix = cb.tiny_traffic(ENTRY["traffic"])
    kind = harness.load_module("answers", mix["answer"])
    limits = harness.load_limits(CELL)
    (inputs,) = harness.run_inputs(cfg["N"], mix, 5)
    got = kind.answer(cfg, mix, inputs, harness.Spans())
    assert harness.checks_ok(kind.check(cfg, mix, inputs, got, limits))
    assert not harness.checks_ok(kind.control(cfg, mix, inputs, got,
                                              limits))


def test_bisection_cell_holds_the_reference_past_an_infeasible_probe():
    """At PF(31) the mix's deployment has its saturation just above
    0.265625, a probe that follows an infeasible one (0.28125).  Started
    from that infeasible iterate it ran out of steps reading max_util > 1
    and the answer fell a whole cell low; started from the last feasible
    probe's split, the answer's cell meets the reference interval."""
    cfg = cb.tiny_config(ENTRY["config"], q=31)
    mix = harness.load_traffic(ENTRY["traffic"])
    kind = harness.load_module("answers", mix["answer"])
    limits = harness.load_limits(CELL)
    (inputs,) = harness.run_inputs(cfg["N"], mix, 7)
    got = kind.answer(cfg, mix, inputs, harness.Spans())
    checks = kind.check(cfg, mix, inputs, got, limits)
    assert harness.checks_ok(checks), checks
    assert checks["sat_off"]["value"] == 0.0, (got["value"], checks)


def _alter_value(monkeypatch):
    from repro.simulation import fluid

    real = fluid.saturation_throughput

    def altered(fp, **kw):
        res = real(fp, **kw)
        res.value = min(1.0, res.value + 0.25)
        return res
    monkeypatch.setattr(fluid, "saturation_throughput", altered)


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


@pytest.mark.parametrize("fault", [_alter_value, _half_the_flows],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_in_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    assert not one_run()["correct"]
