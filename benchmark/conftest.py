"""The tiny size of each configuration that ``tests/tiny.py`` does not
size yet, so its throwaway root holds every cell of ``BENCHMARK.json``."""

import os
import sys

TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests")
if TESTS not in sys.path:
    sys.path.insert(0, TESTS)

import tiny  # noqa: E402

#: 4 shards of the presence-1m tiny size
tiny.SIZES.setdefault("presence-4m", {"players": 16384, "games": 256})
