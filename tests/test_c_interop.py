"""Byte-level interop against a real compiled-C reference-protocol peer
(SURVEY.md §7.4 hard part 5).

`native/stc_harness.c` is a fresh C implementation of the reference wire
protocol + codec spec (reference src/sharedtensor.c:106-189 BEHAVIOR, per
SURVEY.md Appendix B — not a copy). A wire-compat framework node and the C
peer exchange real codec frames over loopback TCP; both sides must converge
to seed + both adds — the reference README.md:24 eventual-consistency
contract, proven across the language boundary.
"""

import os
import socket
import subprocess
import time

import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.comm.peer import create_or_fetch
from shared_tensor_tpu.config import Config, TransportConfig

NATIVE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
HARNESS = os.path.join(NATIVE, "stc_harness")


from tests._ports import free_port as _free_port


@pytest.fixture(scope="module")
def harness_bin():
    proc = subprocess.run(
        ["make", "-C", NATIVE, "stc_harness"], capture_output=True, text=True
    )
    if proc.returncode != 0 or not os.path.exists(HARNESS):
        pytest.skip(f"no C toolchain to build stc_harness: {proc.stderr[-300:]}")
    return HARNESS


def test_c_peer_mutual_convergence(harness_bin):
    """C peer joins a wire-compat master, both add known deltas, both
    replicas converge to seed + sum of adds within codec tolerance."""
    n = 256
    port = _free_port()
    # Homogeneous-magnitude seed: 1 bit/elem/frame convergence (BASELINE.md
    # curve), exact in ~30 frames at loopback frame rates.
    seed = jnp.asarray(np.linspace(0.5, 1.5, n).astype("f4"))
    cfg = Config(transport=TransportConfig(peer_timeout_sec=10.0, wire_compat=True))

    peer = create_or_fetch("127.0.0.1", port, seed, cfg)
    try:
        c = subprocess.Popen(
            # 12 s runtime: the harness deadline is wall-clock, and under
            # full-suite load on this 1-vCPU box a 6 s window intermittently
            # closed before the master's +2 add finished streaming (one
            # observed suite failure; the interior-node sibling uses 10 s)
            [harness_bin, "127.0.0.1", str(port), str(n), "12.0", "1.0"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        time.sleep(1.0)  # C peer is joined and streaming; now add our delta
        peer.add(jnp.full((n,), 2.0, jnp.float32))

        out, err = c.communicate(timeout=40)
        assert c.returncode == 0, err[-500:]

        expected = np.asarray(seed) + 1.0 + 2.0
        c_values = np.array([float(x) for x in out.split()], dtype="f4")
        assert c_values.shape == (n,), c_values.shape
        np.testing.assert_allclose(c_values, expected, atol=0.02)

        # our side must have converged to the same state (C's +1 arrived)
        deadline = time.time() + 10
        while time.time() < deadline:
            ours = np.asarray(peer.read())
            if np.allclose(ours, expected, atol=0.02):
                break
            time.sleep(0.25)
        np.testing.assert_allclose(ours, expected, atol=0.02)
    finally:
        peer.close()


def test_c_peer_receives_seed_state(harness_bin):
    """A C joiner with add=0 must end up holding the master's seed — the
    state-transfer-through-codec join (reference src/sharedtensor.c:379-391)
    working for a peer we didn't write."""
    n = 128
    port = _free_port()
    seed = jnp.asarray((np.arange(n) % 7 + 1).astype("f4") * 0.25)
    cfg = Config(transport=TransportConfig(peer_timeout_sec=10.0, wire_compat=True))

    peer = create_or_fetch("127.0.0.1", port, seed, cfg)
    try:
        c = subprocess.Popen(
            [harness_bin, "127.0.0.1", str(port), str(n), "5.0", "0.0"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        out, err = c.communicate(timeout=30)
        assert c.returncode == 0, err[-500:]
        c_values = np.array([float(x) for x in out.split()], dtype="f4")
        np.testing.assert_allclose(c_values, np.asarray(seed), atol=0.02)
    finally:
        peer.close()


def test_c_peer_as_interior_node(harness_bin):
    """The C peer as an INTERIOR node (round-3 verdict Weak #5): master
    (python, max_children=1) <- C harness (children=1) <- python joiner.
    The master's single child slot is taken by the C peer, so the second
    python peer's join walk gets redirected ('N' + raw sockaddr) to the C
    node, which accepts it. All three replicas must then converge to
    seed + every add — which can only happen if the C node FLOODS frames
    between its links with per-hop re-quantization through its own
    residuals (reference src/sharedtensor.c:124-127)."""
    n = 192
    port = _free_port()
    seed = jnp.asarray(np.linspace(0.25, 1.25, n).astype("f4"))
    cfg = Config(
        transport=TransportConfig(
            peer_timeout_sec=10.0, wire_compat=True, max_children=1
        )
    )
    expected = np.asarray(seed) + 2.0 + 1.0 + 0.5

    master = create_or_fetch("127.0.0.1", port, seed, cfg)
    leaf = None
    try:
        c = subprocess.Popen(
            [harness_bin, "127.0.0.1", str(port), str(n), "10.0", "1.0", "1"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        time.sleep(1.0)  # C interior is joined + listening
        # this join MUST walk through the master's redirect to the C node
        leaf = create_or_fetch(
            "127.0.0.1", port, jnp.zeros((n,), jnp.float32), cfg
        )
        assert not leaf.is_master
        master.add(jnp.full((n,), 2.0, jnp.float32))
        leaf.add(jnp.full((n,), 0.5, jnp.float32))

        out, err = c.communicate(timeout=40)
        assert c.returncode == 0, err[-500:]
        c_values = np.array([float(x) for x in out.split()], dtype="f4")
        # the C interior saw both directions' mass
        np.testing.assert_allclose(c_values, expected, atol=0.05)

        # both python ends converged THROUGH the C node's flood: the
        # master's +2 reached the leaf only via C, and the leaf's +0.5
        # reached the master only via C
        deadline = time.time() + 15
        while time.time() < deadline:
            m = np.asarray(master.read())
            l = np.asarray(leaf.read())
            if np.allclose(m, expected, atol=0.05) and np.allclose(
                l, expected, atol=0.05
            ):
                break
            time.sleep(0.25)
        np.testing.assert_allclose(np.asarray(master.read()), expected, atol=0.05)
        np.testing.assert_allclose(np.asarray(leaf.read()), expected, atol=0.05)
    finally:
        if leaf is not None:
            leaf.close()
        master.close()
