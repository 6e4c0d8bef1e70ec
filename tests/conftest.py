"""Test configuration: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's "multi-node without a cluster" testing stance
(reference: 3rdparty/ps-lite/tests/local.sh runs schedulers/servers/workers
as localhost processes): unit tests run single-process, state-machine tests
use a fake in-process transport, integration tests spawn real subprocesses.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
