"""On-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

  * ``configs/<config>.json`` — the configuration as it is run;
  * ``traffic/<traffic>.json`` — the mix: its ``kind`` names the runner
    (``runners/<kind>.py``), the rest are its parameters and the limits
    of its correctness comparison;
  * ``metrics/<metric>.py`` — one per-layer metric reader;
  * ``reference/<family>.py`` and ``costs/<family>.py`` — the plain
    float32 reference and the FLOP/byte counts of a model family.

The program under test lives in ``<repo>/src``; importing this package
puts it on ``sys.path``.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
