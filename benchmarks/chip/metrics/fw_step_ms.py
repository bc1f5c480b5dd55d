"""`fw_step_ms`: milliseconds per Frank-Wolfe step: the window's synced
`solve` spans over its Frank-Wolfe steps."""


def read(ctx):
    solve = ctx["spans"].get("solve")
    steps = sum(a.get("iters", 0) for a in ctx["answers"])
    return 1e3 * sum(solve) / steps if solve and steps else None
