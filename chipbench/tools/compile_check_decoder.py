"""Compile the ``train_decoder`` cells' programs at their real size for a v5e that
is described and not attached (the fused train step with and without the
sync, and the two programs of the job's checks), and print the seconds each
took and its ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 chipbench/tools/compile_check_decoder.py [cell ...]

Compiling is not running: this says what fits and what the compiler refuses,
at no chip time, and nothing about speed. PERF.md records its figures.
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

    from chipbench import harness
    from chipbench.jobs.train_decoder import check_programs, model_config, modules
    from shared_tensor_tpu.ops import codec_pallas
    from shared_tensor_tpu.ops.table import make_spec
    from shared_tensor_tpu.parallel import PeerSyncState, make_mesh, state_sharding
    from shared_tensor_tpu.train import build_train_step

    codec_pallas._interpret = lambda: False  # what a tpu backend selects
    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cells = [harness.load_json(ROOT / "chipbench" / "workloads" / f"{w['name']}.json")
             for w in manifest["workloads"]]
    wanted = argv or [c["name"] for c in cells if c["job"] == "train_decoder"]
    for cell in (c for c in cells if c["name"] in wanted):
        cfg = harness.load_json(harness.config_file(manifest, cell["config"]))
        model, reference, counts = modules(cfg)
        mcfg = model_config(model, cfg)
        n_peer, n_shard = cell["mesh"]
        mesh = make_mesh(n_peer, n_shard, devices=devices)
        sh = state_sharding(mesh)
        arg = lambda shape, dtype, pspec: jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, pspec))
        spec = make_spec(jax.eval_shape(
            lambda k: model.init_params(k, mcfg), jax.random.key(0)))
        seq, batch = cell["sequence_length"], cell["per_peer_batch"]
        print(json.dumps({
            "cell": cell["name"], "leaves": spec.num_leaves, "params": spec.total_n,
            "padded": spec.total, "attn_block": mcfg.attn_block, "expert_tile": mcfg.expert_tile,
            "analytic_train_flops_per_step":
                counts.train_flops_per_sequence(cfg, seq) * batch * n_peer}), flush=True)
        block = arg((n_peer, spec.total), jnp.float32, sh.spec)
        tokens = arg((n_peer, batch, seq), jnp.int32, P(sh.spec[0]))
        def report(name, lowered):
            t0 = time.perf_counter()
            compiled = lowered.compile()
            m = compiled.memory_analysis()
            text = compiled.as_text()
            print(json.dumps({
                "program": f"{cell['name']}: {name}",
                "compile_s": round(time.perf_counter() - t0, 1),
                "argument_gb": m.argument_size_in_bytes / 1e9,
                "output_gb": m.output_size_in_bytes / 1e9,
                "alias_gb": m.alias_size_in_bytes / 1e9,
                "temp_gb": m.temp_size_in_bytes / 1e9,
                "tpu_custom_call": text.count("tpu_custom_call"),
                "while": text.count(" while("),
            }), flush=True)

        for sync in (True, False):
            step = build_train_step(
                mesh, spec, lambda p, b: model.loss_fn(p, b, mcfg), sync=sync)
            report(f"fused train step sync={sync}", step.lower(
                PeerSyncState(block, block), None, tokens, arg((), jnp.float32, P())))
        # the checks' programs hold one peer's arrays, on one device
        one = lambda shape, dtype: arg(shape, dtype, P())
        params = jax.tree.map(lambda s: one(s.shape, s.dtype), jax.eval_shape(
            lambda k: model.init_params(k, mcfg), jax.random.key(0)))
        at = one((cfg["checks"]["reference_positions"],), jnp.int32)
        choices = [[one((seq, mcfg.num_experts_per_tok), jnp.int32)] * mcfg.expert_layers] * batch
        sgd_step, reference_checked = check_programs(
            model, reference, mcfg, cfg, cfg["checks"]["choices_margin"])
        report("bare step (checks a, c)", jax.jit(sgd_step, donate_argnums=0).lower(
            params, one((batch, seq), jnp.int32), at, one((), jnp.float32)))
        report("reference (checks a, b)", jax.jit(reference_checked).lower(
            params, one((batch, seq), jnp.int32), choices, at))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
