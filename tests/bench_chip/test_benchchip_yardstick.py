"""The yardstick's own pieces: traffic from the seed, nearest-rank tails,
the peaks table, kernel bytes from shapes, the result line."""

import json

import numpy as np
import pytest

import _chipbench as cb

harness = cb.harness


@pytest.mark.parametrize("seed", [0, 1, 7, 2 ** 31 + 5, 9_876_543_210_123])
@pytest.mark.parametrize("n", [57, 993, 6321])
def test_derangement_has_no_fixed_point_and_one_flow_per_router(seed, n):
    inp = harness.draw_inputs(n, seed)
    assert len(inp["src"]) == len(inp["dst"]) == n
    assert (np.sort(inp["dst"]) == np.arange(n)).all()
    assert not (inp["dst"] == inp["src"]).any()
    again = harness.draw_inputs(n, seed)
    assert (again["dst"] == inp["dst"]).all()
    assert again["program_seed"] == inp["program_seed"]


@pytest.mark.parametrize("n", [57, 993])
def test_each_seed_draws_its_own_deployment(n):
    draws = [harness.draw_inputs(n, s) for s in (1, 2, 2 ** 33 + 1)]
    assert len({tuple(d["dst"]) for d in draws}) == 3
    assert len({d["program_seed"] for d in draws}) == 3


def test_a_pool_gives_every_seed_the_same_deployments_in_its_order():
    mix = {"pool": {"seed": 77, "size": 3}}
    runs = {s: harness.run_inputs(57, mix, s) for s in range(8)}
    keys = {s: [tuple(r["dst"]) for r in rs] for s, rs in runs.items()}
    assert all(sorted(k) == sorted(keys[0]) for k in keys.values())
    assert len(set(keys[0])) == 3
    assert len({tuple(k) for k in keys.values()}) > 1  # orders differ
    alone = harness.run_inputs(57, {}, 5)
    assert len(alone) == 1
    assert (alone[0]["dst"] == harness.draw_inputs(57, 5)["dst"]).all()


@pytest.mark.parametrize("passes_of", [1, 3])
def test_window_answers_whole_passes(passes_of):
    win = harness.Window(0.0, passes_of)
    win.run(lambda i: {"i": i})
    assert len(win.results) == passes_of
    assert win.per_answer_s == win.elapsed / passes_of


def test_nearest_rank_tails():
    lat = np.arange(1, 1001)
    assert harness.tail_percentiles(lat) == {"p50": 500, "p99": 990,
                                             "p999": 999}
    assert harness.tail_percentiles(np.array([7])) == {"p50": 7, "p99": 7,
                                                       "p999": 7}
    with pytest.raises(ValueError):
        harness.tail_percentiles(np.array([], dtype=int))


def test_peaks_by_device_kind():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v99 imaginary")


def test_path_costs_bytes_at_pf79_shapes():
    import costs

    # F = 6,321 flows (one per router), K = 11 candidates, L = 4 hops
    fkl = 6321 * 11 * 4
    assert costs.path_costs_bytes(6321, 11, 4) == 8 * fkl + 4 * 6321 * 11
    assert costs.path_costs_bytes(6321, 11, 4) == 2_503_116


def test_result_line_keys_and_checks_last():
    checks = {"paths_bad": {"value": 0, "limit": 0}}
    line = json.loads(harness.result_line(
        True, 4, 0, {"setup_s": {"value": 1.5, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, checks,
        {"device_ops": [], "idle_gaps": []}))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert harness.checks_ok(checks)
    assert not harness.checks_ok({"x": {"value": 1, "limit": 0}})


def test_spread_is_quartiles_over_median():
    assert harness.spread([1.0] * 6) == 0.0
    # exclusive quartiles of 1..6 are 1.75 and 5.25, the median 3.5
    assert harness.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


def test_bounds_from_two_sets(tmp_path):
    import bounds

    def runs(tag, values):
        paths = []
        for i, v in enumerate(values):
            p = tmp_path / f"{tag}{i}.out"
            metrics = {"sat_answer_s": {"value": v, "unit": "s"},
                       "setup_s": {"value": 20.0 + i, "unit": "s"}}
            p.write_text("setup_s=1\n" + json.dumps({"metrics": metrics}))
            paths.append(str(p))
        return paths
    out = bounds.main(["--set"] + runs("a", [10.0] * 5 + [10.6])
                      + ["--set"] + runs("b", [10.0, 10.1, 10.0, 10.2,
                                                 10.1, 10.0]))
    sat = out["sat_answer_s"]
    assert sat["widest"] == max(sat["spreads"]) > 0
    assert sat["bound"] == pytest.approx(min(0.25, 5 * sat["widest"]))
    assert out["setup_s"]["bound"] == 0.25
