"""`paths_s`: mean host seconds of the `paths` span over the window's
answers (the harness's span around the paths layer, synced)."""


def read(ctx):
    d = ctx["spans"].get("paths")
    return sum(d) / len(d) if d else None
