"""bench/tests run by hand (`python -m pytest bench/tests -q`), on the CPU
with four virtual devices; they are not part of the repo's tier-1 suite."""
import os
import sys

import jax

jax.config.update("jax_num_cpu_devices", 4)   # before the CPU client exists

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
