"""Tests of the benchmark itself: ``python -m pytest benchmark/tests -q``,
on the CPU. They live here, not under ``tests/``, because the yardstick's
own checks belong to the yardstick."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"
