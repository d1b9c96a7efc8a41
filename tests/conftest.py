"""Test harness: an 8-device virtual CPU mesh.

Multi-chip hardware isn't available in CI; all sharding logic is
validated on a virtual CPU mesh
(``xla_force_host_platform_device_count``), provisioned by
``__graft_entry__._provision_virtual_devices``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _provision_virtual_devices  # noqa: E402

_provision_virtual_devices(8)

import jax  # noqa: E402

from fedml_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

# test time is dominated by compiles and the suite is re-run every PR:
# warm-cache runs cut the fast tier by several minutes
enable_compile_cache()

assert len(jax.devices()) == 8, jax.devices()
