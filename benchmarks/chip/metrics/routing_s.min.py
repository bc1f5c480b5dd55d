"""`routing_s.min`: `routing_s`'s reading, in the cells whose answer time is
`sat_answer_s.min`."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import harness  # noqa: E402

read = harness.load_module("metrics", "routing_s").read
