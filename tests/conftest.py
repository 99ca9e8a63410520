import os
import re
import sys

import pytest

# Tests must never see the dry-run's 512 placeholder devices (see
# launch/dryrun.py which sets XLA_FLAGS itself). Small host-device counts
# ARE allowed: the CI multidevice lane runs the tolerance-tier suites under
# XLA_FLAGS=--xla_force_host_platform_device_count=4 (docs/architecture.md
# §The tolerance tier); tests that need >1 device skip themselves when the
# flag is absent.
_count = re.search(r"xla_force_host_platform_device_count=(\d+)",
                   os.environ.get("XLA_FLAGS", ""))
assert _count is None or int(_count.group(1)) <= 8, \
    "do not run tests with dry-run XLA_FLAGS"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def make_fake_mesh(shape=(16, 16), axes=("data", "model")):
    """Abstract mesh for spec construction (no real devices needed)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))


@pytest.fixture
def fake_mesh():
    """Factory fixture over :func:`make_fake_mesh`."""
    return make_fake_mesh
