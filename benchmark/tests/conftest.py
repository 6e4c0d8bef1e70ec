"""The benchmark's own tests (``python -m pytest benchmark/tests``; not
part of the repo's tier-1 run). They run on the CPU: nothing here is a
chip result."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
