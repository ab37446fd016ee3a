"""The benchmark's own tests: `python -m pytest benchmark/tests -q` with
`JAX_PLATFORMS=cpu`. The pure ones take seconds; test_rehearsal.py drives
whole runs of a tiny cell on the CPU backend, a process each."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
