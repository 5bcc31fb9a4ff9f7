"""Hierarchical tier tests: two pods (disjoint device subsets of the 8-device
virtual mesh) bridged through the TCP tree in one process — the multi-host
story at test scale (ICI inside each pod, the reference's tree between
pods)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._mesh import make_mesh
from shared_tensor_tpu.train import HierarchicalTrainer
from tests.test_peer import _free_port


def _template():
    return {"w": jnp.zeros((8,), jnp.float32)}


def _quad_loss(p, b):
    # pull w toward the batch target; async-DP mixes the pods' targets
    return jnp.mean((p["w"] - b) ** 2)


def _meshes():
    devs = jax.devices()
    return make_mesh(2, 1, devices=devs[:2]), make_mesh(2, 1, devices=devs[2:4])


def _settle(fn, cond, timeout=15.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        fn()
        if cond():
            return True
        time.sleep(0.1)
    return False


def test_add_propagates_between_pods():
    mesh_a, mesh_b = _meshes()
    port = _free_port()
    a = HierarchicalTrainer.create(mesh_a, "127.0.0.1", port, _template(), _quad_loss)
    try:
        b = HierarchicalTrainer.create(mesh_b, "127.0.0.1", port, _template(), _quad_loss)
        try:
            # pod A: every device peer adds 1s (out-of-band update)
            a.pod.add(jnp.ones((a.pod.n_peer, a.pod.spec.total), jnp.float32))
            # intra-pod sync + bridge exchanges until B sees ~2.0 per slot
            # (2 device peers x +1 each)
            def pump():
                batch = jnp.zeros((2, 8), jnp.float32)
                a.step(batch, lr=0.0)
                b.step(batch, lr=0.0)

            ok = _settle(
                pump,
                lambda: np.allclose(
                    np.asarray(b.read(0)["w"]), 2.0, atol=0.05
                ),
            )
            assert ok, np.asarray(b.read(0)["w"])
        finally:
            b.close()
    finally:
        a.close()


def test_two_pod_training_converges_to_mixture():
    """Pod A trains toward +2, pod B toward -2; through the bridge both
    models settle near the mixture (0) instead of their local target —
    proof the cross-pod deltas actually steer training. Pod B runs the
    overlap sync mode (collective under the backward pass) against pod A's
    fused mode: the modes must interoperate through the bridge."""
    mesh_a, mesh_b = _meshes()
    port = _free_port()
    a = HierarchicalTrainer.create(mesh_a, "127.0.0.1", port, _template(), _quad_loss)
    try:
        b = HierarchicalTrainer.create(
            mesh_b, "127.0.0.1", port, _template(), _quad_loss, overlap=True
        )
        try:
            ta = jnp.full((2, 8), 2.0)
            tb = jnp.full((2, 8), -2.0)
            for _ in range(150):
                a.step(ta, lr=0.05)
                b.step(tb, lr=0.05)
                time.sleep(0.002)  # let tree frames flow
            # During live opposing training the pods disagree only by the
            # in-flight delta mass (local-only would sit at +2/-2)...
            wa = float(jnp.mean(a.read(0)["w"]))
            wb = float(jnp.mean(b.read(0)["w"]))
            assert abs(wa) < 1.6, wa
            assert abs(wb) < 1.6, wb
            # ...and once updates stop, the backlog drains and both pods
            # agree — the reference's eventual-consistency contract
            # (README.md:24, "values may overshoot temporarily").
            def quiesce():
                a.step(ta, lr=0.0)
                b.step(tb, lr=0.0)

            def agreed():
                va = float(jnp.mean(a.read(0)["w"]))
                vb = float(jnp.mean(b.read(0)["w"]))
                return abs(va - vb) < 0.05

            assert _settle(quiesce, agreed), (
                float(jnp.mean(a.read(0)["w"])),
                float(jnp.mean(b.read(0)["w"])),
            )
        finally:
            b.close()
    finally:
        a.close()


def test_tiled_pod_converges_with_a_host_peer():
    """A pod whose device table holds a leaf in 8 x 128 tile order (its
    ``[16, 256]``) bridged to a host peer, whose table is row-major: what
    either side adds reaches the other leaf for leaf and element for
    element, so the bridge's order conversions hold both ways."""
    from shared_tensor_tpu.comm.peer import create_or_fetch

    mesh_a, _ = _meshes()
    port = _free_port()
    tpl = {"m": jnp.zeros((16, 256), jnp.float32), "w": jnp.zeros((8,), jnp.float32)}
    host = create_or_fetch("127.0.0.1", port, tpl)
    try:
        pod = HierarchicalTrainer.create(mesh_a, "127.0.0.1", port, tpl, _quad_loss)
        try:
            assert pod.pod.spec.tiled == (True, False)
            assert not any(host.st.spec.tiled)
            ramp = {"m": jnp.arange(16 * 256, dtype=jnp.float32).reshape(16, 256) / 4096,
                    "w": jnp.linspace(-1.0, 1.0, 8, dtype=jnp.float32)}
            # the pod's two device peers each add the ramp, the host its
            # negative halved: every replica settles on 1.5 x the ramp
            from shared_tensor_tpu.ops.table import flatten

            pod.pod.add(jnp.broadcast_to(flatten(ramp, pod.pod.spec), (2, pod.pod.spec.total)))
            host.add(jax.tree.map(lambda x: -0.5 * x, ramp))

            def pump():
                pod.step(jnp.zeros((2, 8), jnp.float32), lr=0.0)

            def settled(read):
                return all(np.allclose(np.asarray(read[k]), 1.5 * np.asarray(ramp[k]), atol=0.02)
                           for k in ramp)

            assert _settle(pump, lambda: settled(pod.read(0)) and settled(host.read())), (
                pod.read(0), host.read())
        finally:
            pod.close()
    finally:
        host.close()


def test_layout_mismatch_rejected():
    from shared_tensor_tpu.comm.peer import create_or_fetch
    from shared_tensor_tpu.train import PodTrainer

    mesh_a, _ = _meshes()
    port = _free_port()
    peer = create_or_fetch("127.0.0.1", port, _template())
    try:
        pod = PodTrainer(mesh_a, {"x": jnp.zeros((3, 3))}, _quad_loss)
        with pytest.raises(ValueError, match="layout"):
            HierarchicalTrainer(pod, peer)
    finally:
        peer.close()


def test_pod_bridge_churn_mid_training():
    """Kill a POD BRIDGE peer mid-training (round-2 verdict item 7): four
    2-device pods form the tree; the one that is a mid-tree parent dies while
    every pod is actively training; its orphan re-grafts (LINK_DOWN ->
    carry-residual -> rejoin) under live PodTrainers, and the survivors
    converge to agreement once updates stop — no pod's progress is lost."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    meshes = [make_mesh(2, 1, devices=devs[2 * i : 2 * i + 2]) for i in range(4)]
    port = _free_port()
    from shared_tensor_tpu.config import Config, TransportConfig

    cfg = Config(
        transport=TransportConfig(peer_timeout_sec=5.0, max_rejoin_attempts=16)
    )
    pods = {}
    try:
        for name, mesh in zip("mabc", meshes):
            pods[name] = HierarchicalTrainer.create(
                mesh, "127.0.0.1", port, _template(), _quad_loss, peer_config=cfg
            )
        targets = {"m": 2.0, "a": -2.0, "b": 1.0, "c": -1.0}
        batches = {n: jnp.full((2, 8), t) for n, t in targets.items()}
        # train everyone a bit so real residual mass is in flight
        for _ in range(30):
            for n, tr in pods.items():
                tr.step(batches[n], lr=0.05)
            time.sleep(0.002)
        # the mid-tree parent: a non-master bridge peer with a child link
        parent = next(
            n for n, tr in pods.items()
            if not tr.peer.is_master and len(tr.peer.node.links) > 1
        )
        pods.pop(parent).close()
        survivors = pods

        # keep training through the churn (the orphan re-grafts underneath)
        for _ in range(30):
            for n, tr in survivors.items():
                tr.step(batches[n], lr=0.05)
            time.sleep(0.002)

        # stop updating; all surviving pods must agree (eventual consistency
        # across the re-grafted tree, reference README.md:24)
        def quiesce():
            for n, tr in survivors.items():
                tr.step(batches[n], lr=0.0)

        def agreed():
            means = [float(jnp.mean(tr.read(0)["w"])) for tr in survivors.values()]
            return max(means) - min(means) < 0.05

        # 120 s: each poll iteration runs three jitted pod steps plus tree
        # frames on this 1-vCPU box; under concurrent-suite load 60 s left
        # too little margin (observed flake) while convergence itself is
        # geometric and finishes in a few seconds unloaded.
        assert _settle(quiesce, agreed, timeout=120), {
            n: dict(
                mean=float(jnp.mean(tr.read(0)["w"])),
                uplink=tr.peer.node.uplink,
                links=tr.peer.node.links,
                master=tr.peer.is_master,
                err=str(tr.peer._error),
            )
            for n, tr in survivors.items()
        }
        # and training actually mixed: the agreed consensus cannot equal
        # EVERY pod's own target simultaneously (targets differ by >= 1.0),
        # so agreement alone proves cross-pod deltas steered the models; no
        # per-pod distance assertion (the consensus may legitimately settle
        # near one pod's target depending on kill timing and mixing order)
    finally:
        for tr in pods.values():
            tr.close()
