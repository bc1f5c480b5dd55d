"""`fw_iters`: Frank-Wolfe steps per answer (`Certificate.iters`, summed
over the bisection's probes), mean over the window's answers."""


def read(ctx):
    it = [a["iters"] for a in ctx["answers"] if "iters" in a]
    return sum(it) / len(it) if it else None
