"""benchmarks/tests runs on the CPU only; nothing here measures."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
