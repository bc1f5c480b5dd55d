"""`packet_cycle_ms`: milliseconds per simulated cycle: the window's
synced `scan` spans (`simulate_packets`, outcomes read back) over the
cycles they simulated."""


def read(ctx):
    scan = ctx["spans"].get("scan")
    if not scan:
        return None
    return 1e3 * sum(scan) / (len(scan) * ctx["traffic"]["params"]["cycles"])
