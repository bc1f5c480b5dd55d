"""The reduction from a profiler trace to device numbers, on a small
recorded trace: busy union, idle share, kernel time by scope, and the
span each idle gap is attributed to; and the reading of the HLO that a
trace records, which gives each op its source scopes."""

import json
import os

import pytest

import _chipbench as cb  # noqa: F401  (puts benchmarks/chip on the path)
import reduce_trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_small.json")


@pytest.fixture()
def events():
    with open(FIXTURE) as f:
        return json.load(f)


def test_union_merges_overlaps():
    assert reduce_trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [
        (1, 4), (5, 8)]


def test_busy_and_idle_share(events):
    out = reduce_trace.reduce(events)
    # busy: [3200, 3700) + [6000, 10500) ns (the loop holds its ops); the
    # op after the window does not count
    assert out["busy_s"] == pytest.approx(5000e-9)
    assert out["window_s"] == pytest.approx(10000e-9)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.5)


def test_kernel_time_by_scope(events):
    # two gathers and the reduction fused with the argmin are under the
    # scope; the loop that holds them is not work of its own; a call is
    # one run of the scope's gather
    out = reduce_trace.reduce(events,
                              scopes={"minplus.path_costs": "gather"})
    scope = out["scopes"]["minplus.path_costs"]
    assert scope["seconds"] == pytest.approx(2500e-9)
    assert scope["executions"] == 2
    assert reduce_trace.reduce(events, scopes={"nope": "gather"})["scopes"][
        "nope"] == {"seconds": 0.0, "executions": 0}


def test_idle_gaps_named_by_innermost_span(events):
    gaps = dict(reduce_trace.reduce(events)["breakdown"]["idle_gaps"])
    # [1000, 3200) is graph, then routing; [3700, 6000) routing, then
    # paths; [10500, 11000) solve
    assert gaps == pytest.approx({"graph": 2000e-9, "routing": 500e-9,
                                  "paths": 2000e-9, "solve": 500e-9})


def test_top_device_ops(events):
    ops = reduce_trace.reduce(events)["breakdown"]["device_ops"]
    assert ops[0] == ["fusion.2", pytest.approx(2000e-9)]
    assert [name for name, _ in ops] == ["fusion.2", "fusion.5", "fusion.1",
                                         "fusion.3"]


def test_missing_window_is_an_error(events):
    events["host"] = [h for h in events["host"] if h[0] != "traced_answer"]
    with pytest.raises(ValueError):
        reduce_trace.reduce(events)


def _hlo_proto(module: bytes) -> bytes:
    """An HloProto (its field 1 the module), as the profiler records it."""
    n, size = len(module), bytearray()
    while True:
        size.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return b"\x0a" + bytes(size) + module


def test_op_tags_name_the_scopes_an_op_fuses():
    import jax
    import jax.numpy as jnp

    def solve(delay, eidx):
        def step(c, _):
            with jax.named_scope("minplus.path_costs"):
                cost = (delay * c)[eidx].sum(-1)
            return c + cost.min(), None
        return jax.lax.scan(step, 1.0, None, length=3)[0]

    compiled = jax.jit(solve).lower(jnp.ones(100),
                                    jnp.zeros((30, 3, 4), jnp.int32)).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    tags = reduce_trace.op_tags(
        _hlo_proto(module.as_serialized_hlo_module_proto()))
    loops = [n for n, (op, _, _) in tags.items() if op == "while"]
    assert loops and not any(reduce_trace.in_scope(
        tags[n][2], "minplus.path_costs") for n in loops)
    calls = [n for n, (op, _, t) in tags.items() if op == "fusion"
             and reduce_trace.in_scope(t, "minplus.path_costs", "gather")]
    assert len(calls) == 1


def test_op_name_of_a_tpu_event():
    assert reduce_trace.op_name(
        "%fusion.410 = f32[285696]{0:T(1024)S(1)} fusion(f32[10924]{0} "
        "%pad.1), kind=kLoop") == "fusion.410"
    assert reduce_trace.op_name("fusion.1") == "fusion.1"
