"""The benchmark's own tests run on the CPU at SF0.01; nothing here describes
or touches a TPU."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

SCALE = 0.01
