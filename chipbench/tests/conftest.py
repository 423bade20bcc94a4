"""The benchmark's own tests run on the CPU, at tiny sizes:

    python -m pytest chipbench/tests -q

They check the yardstick (generator, reducer, operation counts, result
line) and rehearse both runners; they print no device metric.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
