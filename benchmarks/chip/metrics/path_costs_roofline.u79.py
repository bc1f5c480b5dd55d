"""`path_costs_roofline.u79`: `path_costs_roofline`'s reading, in
`pf79_ugal.sat` (bytes from its configuration's N, K and L)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import harness  # noqa: E402

read = harness.load_module("metrics", "path_costs_roofline").read
