"""The table cells' two programs compile for a v5e that is described and not
attached: the benchmark's own jitted ``add_updates_raw`` with the coefficient
inside, and the fused sync step, on mesh (4,1), at the rehearsal's size.

The real sizes are compiled by ``chipbench/tools/compile_check.py`` by hand
(it takes minutes; PERF.md records what it printed). As in
tests/test_tpu_compile.py, the topology is described inside a fixture, never
at import, and this is the one file of tests/chipbench/ that does so."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.jobs.table_sync import leaf_layout  # noqa: E402
from shared_tensor_tpu.ops import codec_pallas, table  # noqa: E402
from shared_tensor_tpu.parallel import (  # noqa: E402
    PeerSyncState, build_sync_step, make_mesh, state_sharding,
)
from shared_tensor_tpu.parallel.ici import add_updates_raw  # noqa: E402


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"cannot build a v5e:2x2 topology here: {e}")
    return topo.devices


@pytest.fixture
def compiled_pallas(monkeypatch):
    monkeypatch.setattr(codec_pallas, "_interpret", lambda: False)
    monkeypatch.setenv("ST_CODEC", "pallas")


@pytest.mark.parametrize("n_peer", [1, 4])
def test_table_cell_programs_compile_for_v5e(v5e_devices, compiled_pallas, n_peer):
    with open(os.path.join(ROOT, "chipbench", "configs", "olmoe-layer-table.json")) as f:
        cfg = json.load(f)
    layout = leaf_layout(cfg, rehearsal=True)
    spec = table.make_spec(
        {k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in layout.items()})
    mesh = make_mesh(n_peer, 1, devices=v5e_devices)
    sh = state_sharding(mesh).spec
    block = jax.ShapeDtypeStruct(
        (n_peer, spec.total), jnp.float32, sharding=NamedSharding(mesh, sh))
    coeff = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, P()))
    state = PeerSyncState(block, block)

    add_scaled = jax.jit(lambda st, u, c: add_updates_raw(st, c * u), donate_argnums=(0,))
    added = add_scaled.lower(state, block, coeff).compile()
    # the state is donated: no table-sized product is stored beside it
    assert added.memory_analysis().temp_size_in_bytes < spec.total * 4

    text = build_sync_step(mesh, spec).lower(state).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # quantize and apply
    assert ("all-gather" in text) == (n_peer > 1)
