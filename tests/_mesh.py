"""make_mesh for tests: skip (not fail) when the backend is too small.

The suite normally runs on the 8-device virtual CPU mesh (conftest.py), where
every mesh shape fits. Under ``ST_TEST_PLATFORM=tpu`` the same tests compile
on the chips the host has — one or four — so tests whose mesh needs more
devices than exist must SKIP, exactly like the existing 8-device guard in
test_hierarchical.py, rather than fail the on-chip run.
"""

import os

import pytest

from shared_tensor_tpu.parallel.mesh import make_mesh as _make_mesh

# Only a deliberate real-hardware run may shrink the suite. On the default
# virtual CPU mesh a too-small backend means the 8-device setup itself broke,
# and that must FAIL, not quietly skip the whole sharded/collective tier.
_REAL_HW = os.environ.get("ST_TEST_PLATFORM", "cpu") != "cpu"


def make_mesh(n_peer=None, n_shard: int = 1, **kw):
    try:
        return _make_mesh(n_peer, n_shard, **kw)
    except ValueError as e:
        if _REAL_HW and "needs" in str(e) and "devices" in str(e):
            pytest.skip(str(e))
        raise
