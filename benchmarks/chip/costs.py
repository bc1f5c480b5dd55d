"""Work a kernel must do, from the shapes alone (the roofline's
numerator).  Independent of how the program implements the kernel."""


def path_costs_bytes(flows: int, candidates: int, hops: int) -> int:
    """Bytes one `path_costs` call must move: the [F, K, L] int32 link ids
    and the float32 delays they select read, the [F, K] float32 costs
    written."""
    fkl = flows * candidates * hops
    return fkl * 4 + fkl * 4 + flows * candidates * 4
