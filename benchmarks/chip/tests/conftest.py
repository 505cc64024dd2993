"""CPU, a few virtual devices, set before JAX starts; the benchmark's
directory and the repository root on the path."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
