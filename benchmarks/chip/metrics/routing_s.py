"""`routing_s`: mean host seconds of the `routing` span over the window's
answers (the harness's span around the routing layer, synced)."""


def read(ctx):
    d = ctx["spans"].get("routing")
    return sum(d) / len(d) if d else None
