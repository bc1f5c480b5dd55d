"""`fw_iters.u79`: `fw_iters`'s reading, in `pf79_ugal.sat`."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import harness  # noqa: E402

read = harness.load_module("metrics", "fw_iters").read
