"""Shared helpers of the chip benchmark's CPU tests: the benchmark's own
modules, imported from ``benchmarks/chip``, and tiny configurations."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import harness  # noqa: E402


def tiny_config(name: str, q: int = 7) -> dict:
    """A cell's configuration cut to PF(q) for a CPU test."""
    cfg = harness.load_config(name)
    cfg.update(q=q, N=q * q + q + 1, radix=q + 1, p=(q + 1) // 2,
               links=(q * q + q + 1) * (q + 1) - (q + 1))
    return cfg


def tiny_traffic(name: str) -> dict:
    mix = harness.load_traffic(name)
    if mix["answer"] == "tail":
        mix["params"].update(offered=0.75, cycles=60, skip_cycles=15)
    return mix
