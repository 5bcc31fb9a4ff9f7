"""Executable evidence for the GSPMD multi-host tier: two REAL processes
form a jax.distributed cluster over loopback, build the global (peer, shard)
mesh with parallel/mesh.py, and run a real cross-process collective. This is
the jax.distributed analog of the reference's N-processes-on-localhost dev
story (SURVEY.md §4.1)."""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent(
    """
    import os, sys
    port, pid = sys.argv[1], int(sys.argv[2])
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from shared_tensor_tpu.parallel.mesh import init_multihost, make_mesh

    idx = init_multihost(f"127.0.0.1:{port}", 2, pid)
    assert idx == pid, (idx, pid)
    assert jax.process_count() == 2
    assert len(jax.devices()) == 4  # 2 procs x 2 virtual devices
    # documented idempotency: a second call must no-op, not raise
    assert init_multihost(f"127.0.0.1:{port}", 2, pid) == pid

    # a real cross-process collective through the coordinator
    from jax.experimental import multihost_utils
    got = multihost_utils.broadcast_one_to_all(np.int32(7 * pid + 3))
    assert int(got) == 3, got  # everyone sees process 0's value

    # the pod mesh spans both processes; psum over the peer axis must sum
    # contributions from devices this process cannot address directly
    from jax import shard_map
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(4, 1)
    local = np.full((2, 8), float(pid + 1), "f4")  # proc0 rows=1, proc1 rows=2
    x = multihost_utils.host_local_array_to_global_array(
        local, mesh, P("peer", None)
    )
    f = jax.jit(
        shard_map(
            lambda a: jax.lax.psum(a, "peer"),
            mesh=mesh, in_specs=P("peer", None), out_specs=P(),
        )
    )
    total = f(x)
    # 2 devices hold 1.0 rows + 2 devices hold 2.0 rows -> psum = 6.0
    np.testing.assert_allclose(
        np.asarray(total.addressable_data(0)), np.full((1, 8), 6.0, "f4")
    )
    print("MULTIHOST_OK", pid)
    """
)


from tests._ports import free_port as _free_port


def test_two_process_gspmd_mesh(tmp_path):
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=REPO,
        )
        for pid in (0, 1)
    ]
    outs = []
    for pid, p in enumerate(procs):
        out, err = p.communicate(timeout=150)
        outs.append((pid, p.returncode, out, err))
    for pid, rc, out, err in outs:
        assert rc == 0, f"proc {pid} rc={rc}\n{err[-1500:]}"
        assert f"MULTIHOST_OK {pid}" in out
