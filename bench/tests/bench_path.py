"""Puts the benchmark's directory and the program's ``src`` on the import
path for the benchmark's tests (imported first by each of them; a
``conftest.py`` here would shadow the one of ``tests/``)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
