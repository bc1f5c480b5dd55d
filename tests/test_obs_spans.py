"""The program's spans, scopes and counters: a `Recorder` span is a
`jax.profiler.TraceAnnotation` with its parent, request and self time; the
`NullRecorder` is none of these and `repro.obs` never imports jax; the
host layers (graph, routing, paths, packet workload) open their spans;
the Frank-Wolfe step and the packet cycle carry their scopes in the
lowered programs; a profiler capture holds the program's spans on the
clock of its device ops."""
import glob
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.polarfly import build_polarfly
from repro.core.routing import build_blocked_routing, build_routing
from repro.obs import NullRecorder, Recorder, recording
from repro.obs.report import summarize
from repro.simulation import (build_flow_paths, make_pattern, make_workload,
                              fluid, packet)
from repro.simulation.traffic import TrafficPattern

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`, logging its use."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


def _ticking_recorder() -> Recorder:
    ticks = iter(i / 1e6 for i in range(1000))  # 1 us per clock read
    return Recorder(clock=lambda: next(ticks))


def test_recorder_span_is_a_trace_annotation_with_parent_and_request(
        monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    rec = _ticking_recorder()
    rec.request(7)
    with rec.span("answer"):
        with rec.span("paths.sweep"):
            pass
        rec.counter("blockwise.retrace", 1, fn="f")
    rec.request(None)
    with rec.span("after"):
        pass
    assert ann.log == [("enter", "answer"), ("enter", "paths.sweep"),
                       ("exit", "paths.sweep"), ("exit", "answer"),
                       ("enter", "after"), ("exit", "after")]
    ev = {e["name"]: e for e in rec.events()}
    assert ev["paths.sweep"]["parent"] == "answer"
    assert "parent" not in ev["answer"] and "parent" not in ev["after"]
    assert ev["answer"]["request"] == ev["paths.sweep"]["request"] == 7
    assert ev["blockwise.retrace"]["request"] == 7
    assert "request" not in ev["after"]


def test_self_time_leaves_out_the_spans_inside():
    rec = _ticking_recorder()
    with rec.span("outer"):          # reads the clock at 1 and 8 us
        with rec.span("inner"):      # 2, 3
            pass
        with rec.span("inner"):      # 4, 7
            with rec.span("leaf"):   # 5, 6
                pass
    rows = rec.span_summary()
    assert rows["outer"]["total_us"] == 7.0
    assert rows["outer"]["self_us"] == 3.0   # 7 - (1 + 3)
    assert rows["inner"]["total_us"] == 4.0
    assert rows["inner"]["self_us"] == 3.0   # 4 - 1
    assert rows["leaf"]["self_us"] == rows["leaf"]["total_us"] == 1.0
    # a trace read back from its events gives the same table
    assert summarize(rec.events())["spans"] == rows


def test_null_recorder_enters_no_annotation(monkeypatch):
    def refuse(name):
        raise AssertionError("the NullRecorder entered an annotation")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    rec = NullRecorder()
    rec.request(3)
    with recording(rec):
        build_polarfly(5)
    assert rec.events() == [] and rec.span_summary() == {}


def test_repro_obs_stays_free_of_jax():
    code = ("import sys\n"
            "from repro.obs import Recorder\n"
            "rec = Recorder()\n"
            "with rec.span('a'):\n"
            "    with rec.span('b'):\n"
            "        pass\n"
            "assert rec.events()[0]['parent'] == 'a'\n"
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n"
            "print('NO_JAX_OK')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "NO_JAX_OK" in r.stdout


def _min_answer(q: int = 7):
    """graph -> routing on the blockwise sharded backend -> min paths ->
    device arrays, as the chip benchmark answers them."""
    g = build_polarfly(q).graph
    rt = build_blocked_routing(g, backend="sharded", devices=1)
    src = np.arange(g.n, dtype=np.int32)
    pat = TrafficPattern("perm", src, np.roll(src, 1),
                         np.full(g.n, 4.0, np.float32), 4)
    fp = build_flow_paths(rt, pat, "min")
    jax.block_until_ready(fp.device_arrays())
    return fp


def test_host_layers_open_their_spans():
    rec = Recorder()
    with recording(rec):
        with rec.span("answer"):
            _min_answer()
    evs = [e for e in rec.events() if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    assert {"polarfly.points", "polarfly.adjacency", "polarfly.classify",
            "routing.diameter", "paths.edges", "paths.sweep", "paths.walk",
            "paths.incidence", "blockwise.block",
            "blockwise.round"} <= names
    parents = {(e["name"], e.get("parent")) for e in evs}
    assert ("blockwise.round", "blockwise.block") in parents
    assert ("polarfly.adjacency", "answer") in parents
    # a sweep and the walks around it never hold each other
    assert all(e.get("parent") not in ("paths.sweep", "paths.walk")
               for e in evs if e["name"] in ("paths.sweep", "paths.walk"))
    # one retrace per device function, each named
    fns = sorted(e["args"]["fn"] for e in rec.events()
                 if e["name"] == "blockwise.retrace")
    assert fns == ["repro.core.routing._bfs_device_fn.fn",
                   "repro.core.routing._dest_device_fn.fn"]


@pytest.mark.parametrize("kind", ["pad", "mxu", "scatter"])
def test_incidence_span_names_the_loads_kind(monkeypatch, kind):
    """The `paths.incidence` span names the link-load kind its upload
    chose, once however often the arrays are asked for."""
    from repro.simulation import paths as paths_mod
    pf = build_polarfly(7)
    rt = build_routing(pf.graph, pf)
    src = np.arange(1, pf.graph.n, dtype=np.int32)
    if kind == "scatter":
        # every router sends to router 0: too skewed to pad with no cap
        monkeypatch.setattr(paths_mod, "_INC_PAD_MAX_ENTRIES", 0)
        dst = np.zeros(len(src), np.int32)
    else:
        dst = np.roll(src, 1)
    if kind == "mxu":
        monkeypatch.setattr(paths_mod, "_MXU_LOADS_PLATFORMS", ("cpu",))
    fp = build_flow_paths(rt, TrafficPattern(
        "perm", src, dst, np.ones(len(src), np.float32), 1), "min")
    rec = Recorder()
    with recording(rec):
        fp.device_arrays()
        fp.device_arrays()
    (ev,) = [e for e in rec.events() if e["name"] == "paths.incidence"]
    assert ev["args"] == {"loads_kind": kind}
    assert fp.device_arrays()[1][0] == kind


def test_packet_workload_span():
    pf = build_polarfly(7)
    rt = build_routing(pf.graph, pf)
    fp = build_flow_paths(rt, make_pattern("uniform", rt, p=4, seed=0),
                          "min")
    rec = Recorder()
    with recording(rec):
        wl = make_workload(fp, 0.3, 40, seed=2)
    (ev,) = [e for e in rec.events() if e["name"] == "packet.workload"]
    assert ev["args"] == {"cycles": 40} and wl.cycles == 40


@pytest.fixture(scope="module")
def pf7_ugal():
    pf = build_polarfly(7)
    rt = build_routing(pf.graph, pf)
    pat = make_pattern("uniform", rt, p=4, seed=0)
    return build_flow_paths(rt, pat, "ugal", k_candidates=4, seed=5)


def _op_names(lowered) -> list:
    """The op_names of a lowered program (its location names)."""
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


@pytest.fixture(scope="module")
def certified_op_names(pf7_ugal):
    fp = pf7_ugal
    eidx, loads_rep, valid, is_min, first_edge, demand, _ = \
        fp.device_arrays()
    return _op_names(fluid._certified_saturation.lower(
        eidx, loads_rep[1:], loads_rep[0], valid, is_min, first_edge,
        demand, fp.num_links, "ugal", 0.05, 256, 3, "float32", 0))


@pytest.mark.parametrize("scope,op", [
    ("fluid.loads", "gather"), ("fluid.cost", "minplus.path_costs"),
    ("fluid.target", "one_hot"), ("fluid.line_search", "scan"),
    ("fluid.certify", "fluid.cost")])
def test_fw_step_scopes_in_the_certified_solve(certified_op_names, scope,
                                               op):
    """Each scope of the certified PF(7) solve names the work it holds."""
    assert any(f"{scope}/" in n and op in n.split(f"{scope}/", 1)[1]
               for n in certified_op_names), scope


@pytest.fixture(scope="module")
def packet_op_names(pf7_ugal):
    wl = make_workload(pf7_ugal, 0.2, 30, seed=1)
    return _op_names(packet._run_batched.lower(
        *packet._arrays(wl, np.zeros(0, np.int64)), e_num=wl.num_links,
        size=wl.size, capacity=wl.capacity, adaptive=wl.adaptive,
        gated=wl.gated, seg0=30, seg1=0))


@pytest.mark.parametrize("scope,op", [
    ("packet.arbitrate", "sort"), ("packet.arbitrate", "scatter"),
    ("packet.queues", "scatter"), ("packet.route", "gather")])
def test_packet_cycle_scopes_in_the_scan(packet_op_names, scope, op):
    assert any(f"{scope}/" in n and op in n.split(f"{scope}/", 1)[1]
               for n in packet_op_names), (scope, op)


def test_profiler_capture_holds_the_program_spans(tmp_path):
    """With a Recorder installed, the program's spans land in the
    profiler's host plane, where the device ops are timed."""
    from jax.profiler import ProfileData

    _min_answer()  # compile outside the capture
    with recording(Recorder()):
        with jax.profiler.trace(str(tmp_path)):
            _min_answer()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_serialized_xspace(open(path, "rb").read())
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events}
    assert {"polarfly.adjacency", "routing.diameter", "paths.sweep",
            "paths.walk", "blockwise.round"} <= names
