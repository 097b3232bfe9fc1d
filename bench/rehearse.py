#!/usr/bin/env python3
"""Compile rehearsal of a configuration's serving step for a described
TPU v5e (no chip attached), to size its paged arena.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py qwen3-1.7b [--num-blocks A B]

Lowers the engine's own step (model pass, paged arena, fused kernel,
sampling) at the configuration's engine shapes — on one chip, or on the
configuration's mesh over a described 2x2 host — compiles it, and prints
``memory_analysis`` per device: arguments (weights, arena, operands) and
temporaries. ``num_blocks`` then fills what the weights and temporaries
leave of the chip's memory, less a margin (``--limit``, ``--margin``):
the step is compiled at two arena sizes, and the bytes it needs are
fitted as a fixed part plus a part per block (the step may hold the
arena more than once).
Nothing runs, so this gives no time; a compile that passes here is not a
chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def rehearse(cfg: dict, arch, num_blocks: int) -> dict:
    """Compile the step for a described v5e; return its memory analysis."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    from repro.configs.base import ModelConfig
    from repro.kernels import common as kernel_common
    from repro.models.api import build_model
    from repro.parallel import sharding as shard_rules
    from repro.runtime.engine import ServingEngine

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # The backend decision as a TPU makes it: kernels compiled by Mosaic.
    kernel_common.interpret_kernels = lambda: False
    eng = cfg["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    model = build_model(ModelConfig(name=cfg["name"],
                                    **arch.program_config_fields(cfg)))
    params = model.abstract_params()
    # A small real arena to construct the engine; the lowered step sees
    # the full-size arena's shapes below.
    engine = ServingEngine(model, params, num_slots=eng["num_slots"],
                           max_seq=eng["max_seq"],
                           chunk_size=eng["chunk_size"],
                           block_size=eng["block_size"], num_blocks=8,
                           paged_attn="fused")
    shapes, paged = model.paged_cache_shapes(
        eng["num_slots"], num_blocks + 1, eng["block_size"])
    is_shape = lambda x: isinstance(x, tuple)
    cache = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jax.numpy.bfloat16),
                         shapes, is_leaf=is_shape)
    if eng.get("mesh"):
        mesh = jax.sharding.Mesh(
            np.array(topo.devices).reshape(eng["mesh"]), ("data", "model"))
        param_sh = shard_rules.serving_param_shardings(params, mesh)
        cache_sh = shard_rules.serving_cache_shardings(
            cache, mesh, tuple(jax.tree.leaves(paged)))
        engine.mesh = mesh
        engine.arena._shardings = cache_sh
    else:
        chip = SingleDeviceSharding(topo.devices[0])
        param_sh = jax.tree.map(lambda _: chip, params)
        cache_sh = jax.tree.map(lambda _: chip, cache)
    place = lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
    engine.params = jax.tree.map(place, params, param_sh)
    engine.arena.buffers = jax.tree.map(place, cache, cache_sh)
    t0 = time.perf_counter()
    compiled = engine.lower_step().compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    return {"num_blocks": num_blocks,
            "compile_host_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "tpu_custom_call": "tpu_custom_call" in text,
            "all_gathers": text.count("all-gather-start")
            + text.count(" all-gather("),
            "all_reduces": text.count("all-reduce-start")
            + text.count(" all-reduce(")}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="configuration name (bench/configs)")
    ap.add_argument("--num-blocks", type=int, nargs=2, default=(1024, 2048),
                    help="the two arena sizes to compile at")
    ap.add_argument("--limit", type=float, default=15.75 * 2 ** 30,
                    help="device memory a program's arguments and "
                         "temporaries may use (v5e: 15.75 GiB)")
    ap.add_argument("--margin", type=float, default=0.75 * 2 ** 30,
                    help="bytes left free for what runs outside the step")
    args = ap.parse_args()
    from bench import spec
    path = spec.BENCH_DIR / "configs" / f"{args.config}.json"
    cfg = json.loads(path.read_text())
    arch = spec.load_module(spec.BENCH_DIR / "configs"
                            / f"{cfg['reference']}.py")
    runs = [rehearse(cfg, arch, nb) for nb in args.num_blocks]
    need = [r["argument_bytes"] + r["temp_bytes"] for r in runs]
    per_block = (need[1] - need[0]) / (args.num_blocks[1]
                                       - args.num_blocks[0])
    fixed = need[0] - per_block * args.num_blocks[0]
    fit = int((args.limit - args.margin - fixed) // per_block)
    print(json.dumps({"compiles": runs, "bytes_per_block": per_block,
                      "fixed_bytes": fixed, "limit_bytes": args.limit,
                      "margin_bytes": args.margin,
                      "num_blocks_that_fit": fit}, indent=1))


if __name__ == "__main__":
    main()
