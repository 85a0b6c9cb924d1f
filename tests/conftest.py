"""Test-suite bootstrap: makes ``src/`` importable so ``pytest`` works
without PYTHONPATH set."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))
