"""`fw_step_ms.u79`: `fw_step_ms`'s reading, in `pf79_ugal.sat`,
where the loads past the one-hot budget show."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import harness  # noqa: E402

read = harness.load_module("metrics", "fw_step_ms").read
