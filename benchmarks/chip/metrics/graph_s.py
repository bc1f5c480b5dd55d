"""`graph_s`: mean host seconds of the `graph` span over the window's
answers (the harness's span around the graph layer, synced)."""


def read(ctx):
    d = ctx["spans"].get("graph")
    return sum(d) / len(d) if d else None
