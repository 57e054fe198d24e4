"""The benchmark's own tests run on the CPU, at sizes a test can hold."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
