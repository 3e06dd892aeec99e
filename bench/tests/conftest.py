"""The benchmark's own tests run on the CPU, with four virtual devices for
the four-client cell.  Run them from the checkout's root:

    python3 -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
