import os
import sys

# src/ layout import path (tests run with or without PYTHONPATH=src)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: no XLA_FLAGS here on purpose — the suite must pass with whatever
# device count it was launched under: 1 (default) and 2 (CI's fast split,
# which exercises the hetero offload executor's real main/offload split).
# Many-device tests spawn subprocesses that set the flag themselves.
