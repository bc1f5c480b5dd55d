"""`packet_prep_s`: mean host seconds per answer from the graph to the
packet workload built (graph, routing, paths and workload spans)."""


def read(ctx):
    sp = ctx["spans"]
    n = len(sp.get("workload", ()))
    if not n:
        return None
    return sum(sum(sp.get(k, ())) for k in ("graph", "routing", "paths",
                                            "workload")) / n
