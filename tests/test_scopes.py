"""The names the chip path gives its own work (PR 27): ``st.*`` scopes in the
compiled sync step and train step, ``st_*`` names on the Pallas calls, the
``st:train.step`` host span, and the by-scope reduction
(utils/profiling.py) on profiles of both programs taken here on the CPU.

The reduction's code path is the chip's: the join of a traced operation's
module and instruction with the compiled program's text. Times read here are
the CPU backend's and are only ever compared with each other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.ops import codec_pallas
from shared_tensor_tpu.ops.table import LeafRows, make_spec
from shared_tensor_tpu.parallel import build_sync_step, init_state, make_mesh
from shared_tensor_tpu.parallel.ici import add_updates
from shared_tensor_tpu.train import PodTrainer
from shared_tensor_tpu.utils import profiling

#: The innermost scopes that partition a sync step (ISSUE 27's table).
SYNC_SCOPES = {"st.leaf_scales", "st.quantize", "st.allgather", "st.apply"}
#: Every path a sync step's operations may sit under.
SYNC_PATHS = {
    "st.codec_send", "st.codec_send/st.leaf_scales",
    "st.codec_send/st.quantize", "st.codec_send/st.allgather",
    "st.codec_apply", "st.codec_apply/st.apply",
}
TRAIN_SCOPES = {"st.grads", "st.unflatten", "st.flatten", "st.update", "st.add_updates"}


def _template():
    k = jax.random.key(0)
    return {
        "w": jax.random.normal(k, (64, 128), jnp.float32),
        "b": jnp.linspace(-1.0, 1.0, 300, dtype=jnp.float32),
    }


def _loss(params, batch):
    # a product and a sum in place of ``x @ w``: the CPU compiler rewrites a
    # batched dot and drops its metadata on the way, the TPU's does not
    x, y = batch
    h = jnp.sum(x[:, :, None] * params["w"][None], axis=1)
    return jnp.mean((jnp.tanh(h) - y) ** 2) + jnp.sum(params["b"] ** 2)


def _trainer(mesh, **kw):
    tr = PodTrainer(mesh, _template(), _loss, **kw)
    n = tr.n_peer
    batch = tr.shard_batch((jnp.ones((n, 8, 64)), jnp.ones((n, 8, 128))))
    return tr, batch


def _innermost(scopes):
    return {profiling.innermost(s) for s in scopes}


# --- (a) the scopes are in the compiled programs -------------------------------


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
@pytest.mark.parametrize("per_leaf", [True, False])
def test_sync_step_carries_every_scope(per_leaf, shape, impl):
    mesh = make_mesh(*shape)
    spec = make_spec(_template())
    state = init_state(mesh, spec, _template())
    step = build_sync_step(mesh, spec, per_leaf=per_leaf, impl=impl)
    scopes = set(profiling.scope_map(step.lower(state).compile()).values())
    assert SYNC_SCOPES <= _innermost(scopes), SYNC_SCOPES - _innermost(scopes)
    assert scopes <= SYNC_PATHS, scopes - SYNC_PATHS


@pytest.mark.parametrize(
    "kw, sync_scopes",
    [({}, True), ({"overlap": True}, True), ({"sync": False}, False)],
    ids=["default", "overlap", "no_sync"],
)
def test_train_step_carries_every_scope(kw, sync_scopes):
    tr, batch = _trainer(make_mesh(4, 1), **kw)
    inner = _innermost(profiling.scope_map(tr.lower(batch).compile()).values())
    want = TRAIN_SCOPES | (SYNC_SCOPES if sync_scopes else set())
    assert want <= inner, want - inner
    if not sync_scopes:
        assert not inner & SYNC_SCOPES


def test_exact_arm_keeps_the_scale_scope():
    mesh = make_mesh(4, 1)
    spec = make_spec(_template())
    step = build_sync_step(mesh, spec, compressed=False)
    scopes = profiling.scope_map(step.lower(init_state(mesh, spec)).compile())
    assert "st.leaf_scales" in set(scopes.values())


# --- (b) the kernels carry their names ------------------------------------------


def _pallas_names(fn, *args):
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


_ROWS = 16
_FLAT = jnp.ones((_ROWS * 128,), jnp.float32)
_TABLES = LeafRows.of(make_spec({"a": jnp.ones(1000), "b": jnp.ones(1024)})).tables(
    codec_pallas.quantize_block_rows(_ROWS)
)


@pytest.mark.parametrize(
    "name, fn, args",
    [
        ("st_quantize_rows",
         lambda s, r: codec_pallas.quantize_rows(s, _TABLES, r),
         (jnp.ones((2,)), _FLAT)),
        ("st_apply_rows_batch",
         lambda s, w, a: codec_pallas.apply_rows_batch(s, _TABLES, w, (a,)),
         (jnp.ones((2, 2)), jnp.zeros((2, 1, 128), jnp.uint32), _FLAT)),
    ],
    ids=lambda v: v if isinstance(v, str) else "",
)
def test_pallas_call_carries_its_name(name, fn, args):
    assert _pallas_names(fn, *args) == [name]


# --- (c) the reduction on CPU profiles of both programs -------------------------


def _check_table(t, expected, compiled_scopes):
    total = sum(t["scopes"].values()) + t["unscoped"]
    assert abs(total - t["self_s"]) <= 1e-9
    for dev in t["per_device"].values():
        assert abs(sum(dev["scopes"].values()) - dev["self_s"]) <= 1e-9
        # nothing here encloses anything: self time is the union of the events
        assert dev["self_s"] <= dev["busy_s"] * 4 + 1e-9
    inner = {}
    for scope, v in t["scopes"].items():
        inner[profiling.innermost(scope)] = inner.get(profiling.innermost(scope), 0.0) + v
    for scope in expected:
        assert inner.get(scope, 0.0) > 0.0, (scope, t["scopes"])
    assert set(t["scopes"]) <= compiled_scopes
    assert t["unscoped"] < 0.10 * t["self_s"], (t["unscoped"], t["self_s"], t["ops"][:12])
    assert 0 < t["operations_scoped"] <= t["operations"]


def test_scope_times_of_a_sync_step(tmp_path):
    mesh = make_mesh(4, 1)
    spec = make_spec(_template())
    state = init_state(mesh, spec, _template())
    step = build_sync_step(mesh, spec, impl="pallas")
    compiled = step.lower(state).compile()
    u = jnp.tile(jnp.linspace(-1.0, 1.0, spec.total, dtype=jnp.float32), (4, 1))
    state = add_updates(state, u)
    state, _ = step(state)
    with profiling.trace(str(tmp_path)):
        for _ in range(3):
            state, scales = step(state)
        jax.block_until_ready(scales)
    smap = profiling.scope_map(compiled)
    t = profiling.scope_times(str(tmp_path), smap, steps=3)
    assert t["devices"] == 4 and t["steps"] == 3
    _check_table(
        t, {"st.leaf_scales", "st.quantize", "st.allgather", "st.apply"},
        set(smap.values()),
    )
    # without the join every operation is unscoped, and the sum still holds
    bare = profiling.scope_times(str(tmp_path), steps=3)
    assert bare["operations_scoped"] == 0
    assert not bare["scopes"] and abs(bare["unscoped"] - bare["self_s"]) <= 1e-9
    assert abs(bare["self_s"] - t["self_s"]) <= 1e-9
    assert "st.codec_send" in profiling.format_table(t)


@pytest.fixture(scope="module")
def traced_train(tmp_path_factory):
    """Four traced steps of a small trainer, after one warm step."""
    d = str(tmp_path_factory.mktemp("train_trace"))
    tr, batch = _trainer(make_mesh(4, 1))
    tr.step(batch)
    first = tr.steps
    with profiling.trace(d):
        for _ in range(4):
            losses, _ = tr.step(batch)
        jax.block_until_ready(losses)
    smap = profiling.scope_map(tr.lower(batch).compile())
    return profiling.scope_times(d, smap, steps=4), smap, first


def test_scope_times_of_a_train_step(traced_train):
    t, smap, _ = traced_train
    _check_table(
        t, {"st.grads", "st.flatten", "st.leaf_scales", "st.quantize", "st.apply"},
        set(smap.values()),
    )


# --- (d) the step span -------------------------------------------------------------


def test_step_span_once_a_step_with_rising_number(traced_train):
    t, _, first = traced_train
    assert t["host_spans"]["st:train.step"]["count"] == 4
    assert [n for n, _, _ in t["step_spans"]] == list(range(first, first + 4))
    ends = [e for _, _, e in t["step_spans"]]
    starts = [s for _, s, _ in t["step_spans"]]
    assert all(e <= s for e, s in zip(ends, starts[1:]))  # one after another


def test_other_host_spans_reach_the_trace(tmp_path):
    tr, batch = _trainer(make_mesh(2, 1))
    with profiling.trace(str(tmp_path)):
        tr.shard_batch((jnp.ones((2, 8, 64)), jnp.ones((2, 8, 128))))
        tr.add(jnp.zeros((2, tr.spec.total), jnp.float32))
        out = tr.read(1)
        losses, _ = tr.step(batch)
        jax.block_until_ready((out, losses))
        PodTrainer(make_mesh(2, 1), _template(), _loss)  # init_state
    spans = profiling.scope_times(str(tmp_path))["host_spans"]
    for name in ("st:shard_batch", "st:add", "st:read_peer", "st:train.step", "st:init_state"):
        assert spans[name]["count"] == 1, (name, spans)


# --- (f) a profiler session changes no result ----------------------------------------


def test_sync_step_bit_equal_under_a_profiler_session(tmp_path):
    mesh = make_mesh(4, 1)
    spec = make_spec(_template())
    step = build_sync_step(mesh, spec, impl="pallas")
    u = jax.random.normal(jax.random.key(7), (4, spec.total), jnp.float32)

    def run():
        state = add_updates(init_state(mesh, spec, _template()), u)
        outs = []
        for _ in range(3):
            state, scales = step(state)
            outs.append(scales)
        return jax.device_get((state.values, state.residual, outs))

    plain = run()
    with profiling.trace(str(tmp_path)):
        traced = run()
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(traced)):
        np.testing.assert_array_equal(a, b)
