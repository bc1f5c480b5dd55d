"""Sharding rules + HLO cost parser units."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.launch.hlo import parse_module

HLO_FIXTURE = """
HloModule jit_f, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %r = f32[] add(%a, %b)
}

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p0 = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} parameter(1)
  %d = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%d), replica_groups=[2,4]<=[8], to_apply=%add
  ROOT %t = (s32[], f32[8,16]) tuple(%p0, %ar)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  ROOT %c = pred[] constant(true)
}

ENTRY %main (x: f32[8,16]) -> f32[8,16] {
  %init = (s32[], f32[8,16]) tuple(s32[] constant(0), %x)
  %wl = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%wl), index=1
}
"""


def test_hlo_parser_trip_counts():
    cost = parse_module(HLO_FIXTURE)
    assert cost.dot_flops == 5 * 2 * 8 * 16 * 16
    # all-reduce: result 8*16*4 bytes, group 4 -> wire 2*S*(3/4), x5 trips
    s = 8 * 16 * 4
    assert abs(cost.coll_wire_bytes["all-reduce"] - 5 * 2 * s * 0.75) < 1e-6
    assert cost.coll_counts["all-reduce"] == 5
    assert cost.unknown_trip_loops == 0


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 2048), st.sampled_from([2, 4, 16]),
       st.sampled_from(["model", "data"]))
def test_spec_for_divisibility(dim, size, axis):
    """spec_for shards iff divisible; never produces invalid specs."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.parallel.sharding import spec_for
    if jax.device_count() < 1:
        pytest.skip("no devices")
    mesh = make_mesh((1,), ("model",))
    spec = spec_for((dim,), ("ff",), mesh)
    if dim % 1 == 0:
        assert spec is not None


def test_spec_rules_fallbacks():
    from repro.launch.mesh import make_mesh
    from repro.parallel.sharding import spec_for
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((1,), ("model",))
    # 14 heads on 1-sized axis: trivially sharded or replicated, never invalid
    s = spec_for((14, 64), ("qheads", "head_dim"), mesh)
    assert isinstance(s, P)
