"""`path_costs_roofline`: the `path_costs` kernel's share of its roofline
in the traced answer, in percent.

The least time is the bytes the algorithm must move per call (the
[F, K, L] link ids and the delays they gather read, the [F, K] costs
written; `costs.path_costs_bytes`, from the configuration's shapes) at
the chip's HBM bandwidth (`peaks.json`), times the calls the trace
counts (runs of the one gather a call makes), over the device time of
the ops that do work under the kernel's `minplus.path_costs` scope
(`reduce_trace`: an op whose HLO, fusions included, holds an instruction
of that scope).  Nothing is read when the scope is gone."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import costs  # noqa: E402
import harness  # noqa: E402


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    scope = trace["scopes"].get("minplus.path_costs")
    if not scope or scope["seconds"] <= 0 or scope["executions"] <= 0:
        return None
    cfg = ctx["config"]
    nbytes = costs.path_costs_bytes(cfg["N"], cfg["K"], cfg["L"])
    peak = harness.load_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    least = scope["executions"] * nbytes / peak
    return 100.0 * least / scope["seconds"]
