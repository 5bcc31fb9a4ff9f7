"""The chip path compiles for a v5e, checked without one.

The installed libtpu can describe a v5e 2x2 topology and run XLA:TPU and
Mosaic for it under JAX_PLATFORMS=cpu, so ``jit(...).lower(...).compile()``
on arguments sharded over that topology is the real compilation: VMEM
overflows, unsupported Mosaic ops and bad shardings fail here, on the CPU,
before anybody spends chip time. Compiling is not running — chip_smoke.py
does that.

The kernels (the codec's and, through the same two switches, the attention's
of ops/attention_pallas.py) are forced out of interpret mode
(``codec_pallas._interpret``) and the codec tier onto Pallas (``ST_CODEC``),
which is what a tpu backend selects by itself; every test asserts the Mosaic custom calls are in the
compiled text. Each test jits a function object of its own, so no trace made
here is ever served to another test.
"""

import json
import re
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from shared_tensor_tpu.models import char_rnn as m
from shared_tensor_tpu.models import gated_swa_moe, mla_moe, swa_moe
from shared_tensor_tpu.ops import codec_pallas, table
from shared_tensor_tpu.parallel import (
    PeerSyncState,
    build_sync_step,
    make_mesh,
    state_sharding,
)
from shared_tensor_tpu.train import build_train_step

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from chipbench.jobs.table_sync import leaf_layout  # noqa: E402


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"cannot build a v5e:2x2 topology here: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def compiled_pallas(monkeypatch):
    monkeypatch.setattr(codec_pallas, "_interpret", lambda: False)
    monkeypatch.setenv("ST_CODEC", "pallas")


@pytest.mark.parametrize(
    "n_peer,n_shard,overlap", [(4, 1, False), (2, 2, True)]
)
def test_flagship_train_step_compiles_for_v5e(
    v5e_devices, n_peer, n_shard, overlap
):
    cfg = m.CharRNNConfig()
    mesh = make_mesh(n_peer, n_shard, devices=v5e_devices)
    spec = table.make_spec(m.init_params(jax.random.key(0), cfg))
    step = build_train_step(
        mesh, spec, lambda p, b: m.loss_fn(p, b, cfg), overlap=overlap
    )

    def arg(shape, dtype, pspec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, pspec)
        )

    sh = state_sharding(mesh).spec
    block = arg((n_peer, spec.total), jnp.float32, sh)
    tokens = arg((n_peer, 16, 256), jnp.int32, P(sh[0]))  # train_bench's shape
    compiled = step.lower(
        PeerSyncState(block, block), None, (tokens, tokens),
        arg((), jnp.float32, P()),
    ).compile()
    # the quantize and the apply kernel
    assert compiled.as_text().count("tpu_custom_call") >= 2


def _olmoe_spec(rehearsal: bool) -> table.TableSpec:
    cfg = json.loads((REPO / "chipbench/configs/olmoe-layer-table.json").read_text())
    return table.make_spec(
        {k: jax.ShapeDtypeStruct(v, jnp.float32)
         for k, v in leaf_layout(cfg, rehearsal).items()}
    )


@pytest.fixture(scope="module")
def olmoe_spec():
    """``olmoe-layer-table`` as the table cells run it: the 201 leaves of one
    OLMoE decoder layer, 3 277 888 rows (shapes only)."""
    spec = _olmoe_spec(rehearsal=False)
    assert spec.num_leaves == 201
    return spec


def _per_row_arrays(text: str, rows: int) -> list[str]:
    """Arrays of the compiled text that hold a number or a few for each of
    ``rows`` table rows: ``f32[rows,1]``, ``s32[rows,1]``, ``f32[rows,K]``.
    XLA pads such an array to 128 lanes (1.68 GB for 13 MB of numbers at
    this size), and it pads the packed words as much where they are laid
    out a table row a row, ``u32[rows,4]`` or ``u32[K,rows,4]``: they travel
    128 words a row, ``u32[rows/32,128]`` (ROADMAP S2)."""
    return sorted(set(re.findall(r"\b[fsu]32\[(?:\d+,)?%d,\d{1,2}\]" % rows, text)))


@pytest.mark.parametrize("k", [1, 4, 8, 16, 64])
def test_apply_table_batch_compiles_for_v5e(v5e_devices, olmoe_spec, k):
    """K = 16 is the device tier's default burst (comm/peer.py) and what
    SharedTensor.receive_frames pads up to (at the seed it asked for more
    VMEM than a kernel gets); K = 64 is its largest: 64 x 201 scales in
    scalar memory beside the leaf tables of 3 202 grid blocks of 1 024 rows,
    a block of K x 32 rows of words. At the table cells' real size, K = 1
    too: the wire's flat words become the kernel's operand by a bitcast
    (XLA spent 7 minutes lowering one frame's reshape to (rows, 4)). But
    for K = 4, whose kernel at that size the (4, 1) sync step below
    compiles: here XLA would spend 38 s a compile on the copy that turns
    ``u32[4, W]``, tiled four frames a tile, into four words arrays
    (ROADMAP S2 (d))."""
    spec = olmoe_spec if k != 4 else _olmoe_spec(rehearsal=True)
    mesh = make_mesh(1, 1, devices=v5e_devices)
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P())
    )
    frames = table.TableFrame(
        arg((k, spec.num_leaves), jnp.float32),
        arg((k, spec.total // 32), jnp.uint32),
    )
    apply = jax.jit(
        partial(table._apply_table_batch.__wrapped__, spec=spec, impl="pallas")
    )
    # the replica alone; with two links' residuals (64 frames and three
    # arrays of 1.68 GB do not fit the chip)
    for n_arrays in (1, 3) if k < 64 else (1,):
        arrays = (arg((spec.total,), jnp.float32),) * n_arrays
        text = apply.lower(arrays, frames).compile().as_text()
        assert "tpu_custom_call" in text, (k, n_arrays)
        assert not _per_row_arrays(text, spec.total // 128), (k, n_arrays)


@pytest.mark.parametrize("n_peer,n_shard", [(1, 1), (4, 1), (2, 2)])
def test_sync_step_compiles_for_v5e_with_no_per_row_operand(
    v5e_devices, olmoe_spec, n_peer, n_shard
):
    """The Pallas tier of tests/test_ici.py's structural test, at the table
    cells' real leaves: around the two kernels the compiled sync step holds no
    gather and no scatter (the leaves' static row ranges do the row <-> leaf
    maps) and no per-row copy of a per-leaf number (the kernels read scales
    and row ranges from scalar memory), on one shard and on two, where the
    XLA row passes switch on the shard index and the kernels' tables are
    picked by it."""
    spec = olmoe_spec
    mesh = make_mesh(n_peer, n_shard, devices=v5e_devices)
    block = jax.ShapeDtypeStruct(
        (n_peer, spec.total), jnp.float32, sharding=state_sharding(mesh)
    )
    text = build_sync_step(mesh, spec).lower(
        PeerSyncState(block, block)
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert not re.findall(r"= \S+ (?:gather|scatter)\(", text)
    assert not _per_row_arrays(text, spec.total // 128 // n_shard)
    assert (" conditional(" in text) == (n_shard > 1)


def test_mla_block_grad_compiles_for_v5e_with_the_attention_kernels(v5e_devices):
    """``value_and_grad`` of one decoder block of models/mla_moe.py at the
    published widths (32 heads of 192 / 128) and 8 192 tokens, mapped over a
    peer axis of one as ``build_train_step`` maps the loss: Mosaic takes the
    two attention kernels at their real tiles and VMEM, both sit in
    ``st.mla.attn``, and no loop is left there (the scan's tiles are gone from
    the chip path). About 8 s."""
    cfg = mla_moe.Config(num_hidden_layers=1, num_nextn_predict_layers=0)
    t = 8192
    mesh = make_mesh(1, 1, devices=v5e_devices)
    arg = lambda *shape: jax.ShapeDtypeStruct(
        (1, *shape), jnp.float32, sharding=NamedSharding(mesh, P())
    )
    prefix = "model.layers.0."
    params = {
        name[len(prefix):]: arg(*shape)
        for name, shape in mla_moe.param_shapes(cfg).items() if name.startswith(prefix)
    }

    def loss(p, x):
        rope = mla_moe.rope_tables(t, cfg.qk_rope_head_dim, cfg.rope_theta)
        return jnp.sum(mla_moe.block(p, x, rope, cfg, is_moe=False)[0])

    text = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
        params, arg(t, cfg.hidden_size)
    ).compile().as_text()
    kernels = {
        name: line for line in text.splitlines() if "tpu_custom_call" in line
        for name in re.findall(r"%(st_attn_\w+?)(?:\.\d+)? = ", line)
    }
    assert sorted(kernels) == ["st_attn_bwd", "st_attn_fwd"]
    for line in kernels.values():
        assert "st.mla.attn" in re.search(r'op_name="([^"]*)"', line).group(1)
    assert not [l for l in text.splitlines() if " while(" in l and "st.mla.attn" in l]


@pytest.mark.parametrize("kind", ["full", "window"])
def test_swa_block_grad_compiles_for_v5e_with_the_attention_kernels(v5e_devices, kind):
    """``value_and_grad`` of one layer of models/swa_moe.py at the published
    widths (28 query heads on 4 K/V heads of 128, 16 of 64 experts held) and
    the whole 16 384-token context, mapped over a peer axis of one: Mosaic
    takes the two attention kernels with a band's tile list (window 4 096)
    and with the triangle's, grouped K/V index maps and float32 ``dk``,
    ``dv`` a query head; both sit in the layer kind's own scope and no loop
    is left there."""
    cfg = swa_moe.Config(num_hidden_layers=1, experts_held=(0, 16))
    t = cfg.sliding_window_size * 4
    window = cfg.sliding_window_size if kind == "window" else None
    mesh = make_mesh(1, 1, devices=v5e_devices)
    arg = lambda *shape: jax.ShapeDtypeStruct(
        (1, *shape), jnp.float32, sharding=NamedSharding(mesh, P())
    )
    prefix = "model.layers.0."
    params = {
        name[len(prefix):]: arg(*shape)
        for name, shape in swa_moe.param_shapes(cfg).items() if name.startswith(prefix)
    }

    def loss(p, x):
        rope = mla_moe.rope_tables(t, cfg.head_dim, cfg.rope_theta) if window else None
        return jnp.sum(swa_moe.block(p, x, rope, cfg, window)[0])

    text = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
        params, arg(t, cfg.hidden_size)
    ).compile().as_text()
    kernels = {
        name: line for line in text.splitlines() if "tpu_custom_call" in line
        for name in re.findall(r"%(st_attn_\w+?)(?:\.\d+)? = ", line)
    }
    assert sorted(kernels) == ["st_attn_bwd", "st_attn_fwd"]
    for line in kernels.values():
        assert f"st.attn.{kind}" in re.search(r'op_name="([^"]*)"', line).group(1)
    assert f"f32[28,{t},128]" in kernels["st_attn_bwd"]  # dk, dv a query head
    assert not [l for l in text.splitlines() if " while(" in l and f"st.attn.{kind}" in l]


@pytest.mark.parametrize("layer", [0, 1])
def test_gated_swa_block_grad_compiles_for_v5e_with_the_attention_kernels(v5e_devices, layer):
    """``value_and_grad`` of one layer of models/gated_swa_moe.py at the
    published widths and 8 192 tokens, mapped over a peer axis of one: layer
    0 (full attention, 48 query heads on 8 K/V heads, YaRN on half a head,
    the dense MLP) and layer 1 (a window of 512 keys, narrower than the
    kernels' widest tile, 72 query heads, 8 of 256 experts held beside the
    shared one). Mosaic takes the two attention kernels at groups of 6 and 9
    with float32 ``dk``, ``dv`` a query head, in the layer kind's scope, and
    the gate's operations sit in ``st.attn.gate``."""
    cfg = gated_swa_moe.Config(num_hidden_layers=2, experts_held=(0, 8))
    t, heads = 8192, cfg.heads(layer)
    kind = "window" if cfg.window(layer) else "full"
    mesh = make_mesh(1, 1, devices=v5e_devices)
    arg = lambda *shape: jax.ShapeDtypeStruct(
        (1, *shape), jnp.float32, sharding=NamedSharding(mesh, P())
    )
    prefix = f"model.layers.{layer}."
    params = {
        name[len(prefix):]: arg(*shape)
        for name, shape in gated_swa_moe.param_shapes(cfg).items() if name.startswith(prefix)
    }

    def loss(p, x):
        rope = gated_swa_moe.layer_rope(cfg, cfg.layer_types[layer], t)
        return jnp.sum(gated_swa_moe.block(p, x, rope, cfg, layer)[0])

    text = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
        params, arg(t, cfg.hidden_size)
    ).compile().as_text()
    kernels = {
        name: line for line in text.splitlines() if "tpu_custom_call" in line
        for name in re.findall(r"%(st_attn_\w+?)(?:\.\d+)? = ", line)
    }
    assert sorted(kernels) == ["st_attn_bwd", "st_attn_fwd"]
    for line in kernels.values():
        assert f"st.attn.{kind}" in re.search(r'op_name="([^"]*)"', line).group(1)
    assert f"f32[{heads},{t},128]" in kernels["st_attn_bwd"]  # dk, dv a query head
    assert not [l for l in text.splitlines() if " while(" in l and f"st.attn.{kind}" in l]
    assert "st.attn.gate" in text
    assert ("st.moe.shared" in text) == (layer > 0) and ("st.ffn" in text) == (layer == 0)


def _computations(text: str) -> dict[str, list[str]]:
    """The compiled text's computations by name, each its instructions' lines."""
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            found[name] = []
        elif name is not None and line.startswith("  "):
            found[name].append(line)
    return found


def _reached(comps: dict[str, list[str]], root: str) -> list[str]:
    """Every instruction of ``root`` and of the computations it calls."""
    seen, todo, lines = set(), [root], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        lines += comps[name]
        for line in comps[name]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)
    return lines


@pytest.mark.parametrize("decoder,tokens,tile", [("swa", 16384, 512), ("mla", 8192, 128)])
def test_expert_layer_grad_compiles_for_v5e_with_token_major_accumulators(
        v5e_devices, decoder, tokens, tile):
    """``value_and_grad`` of the held experts' part of one layer at both
    decoders' cells' shapes (hidden 2 560 = 20 sublanes in tiles of 512 rows;
    2 048 = 16 in tiles of 128), mapped over a peer axis of one: both loops
    (``out``'s and ``du``'s) carry their accumulator ``[T, hidden / 128,
    128]`` in the layout ``{2,1,0:T(8,128)}`` (a token's row is tiles of its
    own), each adds a tile's rows to it by one ``st_moe_combine`` Mosaic call
    an iteration (row copies of one token from and to HBM: what Mosaic
    refuses on the two-dimensional layout), nothing else in a loop's body
    makes or copies an array of the accumulator's size, and it is brought
    back to ``[T, hidden]`` after the loop."""
    if decoder == "swa":
        cfg = swa_moe.Config(num_hidden_layers=1, experts_held=(0, 16), expert_tile=tile)
        width, names, act = cfg.moe_ffn_hidden_size, ("gate", "up", "down"), "relu"
    else:
        cfg = mla_moe.Config(experts_held=(0, 8), expert_tile=tile)
        width, names, act = cfg.moe_intermediate_size, ("gate_proj", "up_proj", "down_proj"), "silu"
    d, k = cfg.hidden_size, cfg.num_experts_per_tok
    mesh = make_mesh(1, 1, devices=v5e_devices)
    arg = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        (1, *shape), dtype, sharding=NamedSharding(mesh, P())
    )
    params = {
        f"experts.{e}.{name}.weight": arg((d, width) if name == names[2] else (width, d))
        for e in range(cfg.experts_held[1]) for name in names
    }

    def loss(p, u, w, idx):
        routed, _ = mla_moe.held_experts(p, u, idx, w, cfg, act, names)
        return jnp.sum(jnp.square(routed))

    text = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2)))).lower(
        params, arg((tokens, d)), arg((tokens, k)), arg((tokens, k), jnp.int32)
    ).compile().as_text()
    carried, flat = f"f32[{tokens},{d // 128},128]", f"f32[{tokens},{d}]"
    result = lambda line: line.split(" = ", 1)[-1]  # "<shape>{layout} <operation>(..."
    operation = lambda line: re.search(r" ([\w\-]+)\(", result(line)).group(1)
    comps = _computations(text)
    loops = [line for line in text.splitlines() if " while(" in line and carried in line]
    assert len(loops) == 2
    for loop in loops:
        assert carried + "{2,1,0:T(8,128)" in loop
        body = _reached(comps, re.search(r"body=%([\w.\-]+)", loop).group(1))
        made = [line for line in body if " = " in line and result(line).startswith((carried, flat))
                and operation(line) not in ("parameter", "get-tuple-element")]
        assert len(made) == 1 and "tpu_custom_call" in made[0], made
        assert re.match(r"\s*%st_moe_combine(\.\d+)? = ", made[0])
        assert "st.moe.combine" in re.search(r'op_name="([^"]*)"', made[0]).group(1)
    # after each loop one transposition, eight tokens' [8, S, 128] to [S, 8, 128]:
    # [T, hidden]'s own tiles, so what follows is a bitcast
    tiles = f"f32[{tokens // 8},{d // 128},8,128]"
    relayouts = [line for line in text.splitlines() if " = " in line
                 and result(line).startswith(tiles) and operation(line) in ("fusion", "copy", "transpose")
                 and "st.moe.combine" in line]
    assert len(relayouts) == 2, relayouts


def _leaf_relayouts(text: str, sizes: set[int]) -> list[str]:
    """Non-bitcast ``reshape`` / ``copy`` / ``transpose`` instructions of a
    compiled text whose result has one of ``sizes`` elements, whatever scope
    XLA names them by: a relayout of the table's view of a leaf is named
    after the cast that follows it, or by nothing."""
    found = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* (?:reshape|copy|transpose)\(.*", text):
        if int(np.prod([int(d) for d in m.group(1).split(",") if d])) in sizes:
            found.append(m.group(0)[:160])
    return found


def test_tiled_leaves_reach_the_step_as_bitcasts_on_v5e(v5e_devices):
    """A per-peer train step over leaves at a decoder's widths: in the pod's
    tile-ordered table (``make_spec(tile_order=True)``) the step's view of
    each ``[256, 3072]`` / ``[2, 256, 3072]`` leaf (``st.unflatten``) and its
    gradient's way back (``st.flatten``) compile with no ``reshape``,
    ``copy`` or ``transpose`` of the leaf; in the row-major table the same
    step relayouts each (four of them)."""
    tpl = {
        "w1": jax.ShapeDtypeStruct((256, 3072), jnp.float32),
        "w2": jax.ShapeDtypeStruct((2, 256, 3072), jnp.float32),
        "norm": jax.ShapeDtypeStruct((3072,), jnp.float32),
    }

    def loss(p, x):
        h = mla_moe._mm(x, p["w1"], jnp.bfloat16) * mla_moe._mm(
            x, p["w2"].reshape(512, 3072), jnp.bfloat16)[:, :256]
        return jnp.mean(jnp.square(h)) + jnp.sum(jnp.square(p["norm"]))

    mesh = make_mesh(1, 1, devices=v5e_devices)
    arg = lambda shape, pspec: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=NamedSharding(mesh, pspec))
    sh = state_sharding(mesh).spec
    found = {}
    for tile_order in (True, False):
        spec = table.make_spec(tpl, tile_order=tile_order)
        block = arg((1, spec.total), sh)
        text = build_train_step(mesh, spec, loss).lower(
            PeerSyncState(block, block), None, arg((1, 128, 3072), P(sh[0])), arg((), P())
        ).compile().as_text()
        assert text.count("tpu_custom_call") >= 2
        found[tile_order] = _leaf_relayouts(text, {256 * 3072, 2 * 256 * 3072})
    assert spec.tiled == (False, False, False) and len(found[False]) == 4, found[False]
    assert table.make_spec(tpl, tile_order=True).tiled == (False, True, True)  # norm, w1, w2
    assert not found[True], found[True]
