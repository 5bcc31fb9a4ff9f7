"""Compile the cells' programs at their real sizes for a v5e that is described
and not attached, and print ``memory_analysis()`` for each.

    JAX_PLATFORMS=cpu python3 chipbench/tools/compile_check.py [cell ...]

Compiling is not running: this says what fits and what the compiler refuses,
at no chip time, and nothing about speed. PERF.md records its figures. The
programs are built by hand from the same public entry points the jobs use,
because the jobs build their meshes from ``jax.devices()``, which here is the
CPU.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["ST_CODEC"] = "pallas"
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench import counts, harness
    from chipbench.jobs.table_sync import leaf_layout
    from shared_tensor_tpu.models import resnet
    from shared_tensor_tpu.ops import codec_pallas
    from shared_tensor_tpu.ops.table import make_spec
    from shared_tensor_tpu.parallel import (
        PeerSyncState, build_sync_step, make_mesh, state_sharding,
    )
    from shared_tensor_tpu.parallel.ici import add_updates_raw
    from shared_tensor_tpu.train import build_train_step

    codec_pallas._interpret = lambda: False  # what a tpu backend selects
    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    wanted = argv or [w["name"] for w in manifest["workloads"]]

    def report(name, lowered):
        t0 = time.perf_counter()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        cost = compiled.cost_analysis() or {}
        print(json.dumps({
            "program": name, "compile_s": round(time.perf_counter() - t0, 1),
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "tpu_custom_call": text.count("tpu_custom_call"),
            "all_gather": text.count("all-gather"),
            "xla_flops": cost.get("flops"),
        }), flush=True)

    for name in wanted:
        cell = harness.load_json(ROOT / "chipbench" / "workloads" / f"{name}.json")
        cfg = harness.load_json(harness.config_file(manifest, cell["config"]))
        n_peer, n_shard = cell["mesh"]
        mesh = make_mesh(n_peer, n_shard, devices=devices)
        sh = state_sharding(mesh)
        arg = lambda shape, dtype, pspec: jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, pspec))
        if cell["job"] == "train":
            model = cfg["model"]
            rcfg = resnet.ResNetConfig(
                stages=tuple(model["stages"]), width=model["width"],
                classes=model["classes"], stem_kernel=model["stem_kernel"],
                stem_stride=model["stem_stride"], stem_pool=model["stem_pool"])
            loss_fn = lambda p, b: resnet.loss_fn(p, b, rcfg)
            shapes = jax.eval_shape(lambda k: resnet.init_params(k, rcfg), jax.random.key(0))
            spec = make_spec(shapes)
            b, hw = cell["per_peer_batch"], model["image_size"]
            block = arg((n_peer, spec.total), jnp.float32, sh.spec)
            batch = (arg((n_peer, b, hw, hw, 3), jnp.float32, P(sh.spec[0])),
                     arg((n_peer, b), jnp.int32, P(sh.spec[0])))
            print(json.dumps({"cell": name, "leaves": spec.num_leaves,
                              "params": spec.total_n,
                              "analytic_train_flops_per_step":
                              counts.resnet_train_flops(model) * b * n_peer}))
            for sync in (True, False):
                step = build_train_step(mesh, spec, loss_fn, sync=sync)
                report(f"{name}: fused train step sync={sync}", step.lower(
                    PeerSyncState(block, block), None, batch, arg((), jnp.float32, P())))
        else:
            layout = leaf_layout(cfg, False)
            spec = make_spec({k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in layout.items()})
            block = arg((n_peer, spec.total), jnp.float32, sh.spec)
            state = PeerSyncState(block, block)
            print(json.dumps({"cell": name, "leaves": spec.num_leaves,
                              "elements": spec.total_n, "padded": spec.total,
                              "kernel_bytes_per_step": counts.sync_step_kernel_bytes(
                                  spec.total // n_shard, n_peer)}))
            add_scaled = jax.jit(lambda st, u, c: add_updates_raw(st, c * u),
                                 donate_argnums=(0,))
            report(f"{name}: add_scaled", add_scaled.lower(
                state, block, arg((), jnp.float32, P())))
            report(f"{name}: sync_step", build_sync_step(mesh, spec).lower(state))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
