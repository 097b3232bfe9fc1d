"""One small tensor-parallel run on four virtual CPU devices, sound or
with the exchange between chips left out; prints ``correct``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/tp_fault_run.py <tmpdir> sound|no_exchange

With ``no_exchange`` every row-parallel projection (attention output and
MLP down, whose contraction axis tensor parallelism splits) keeps each
chip's partial product and skips the sum across chips.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def plant_no_exchange(d_model: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.models import layers
    from repro.parallel import sharding as shard_rules
    real = layers.linear_apply

    def partial_only(p, x, fmt="none", **kw):
        mesh = shard_rules.traced_serving_mesh()
        w = p["w"]
        if mesh is None or w.shape[-2] != d_model or \
                w.shape[-1] == d_model:
            return real(p, x, fmt, **kw)
        local = jax.shard_map(
            lambda xs, ws: jnp.einsum("...k,nk->...n", xs,
                                      ws.astype(xs.dtype)),
            mesh=mesh, check_vma=False, out_specs=P(),
            in_specs=(P(*([None] * (x.ndim - 1)), "model"),
                      P(None, "model")))
        return local(x, w)
    layers.linear_apply = partial_only


def main():
    from bench import harness, spec
    from bench.tests import small
    tmp, mode = Path(sys.argv[1]), sys.argv[2]
    root = small.make_tree(tmp)
    path = root / "configs" / "small.json"
    cfg = json.loads(path.read_text())
    cfg.update(num_key_value_heads=4, head_dim=32,
               tie_word_embeddings=False)
    cfg["engine"]["mesh"] = [1, 4]
    path.write_text(json.dumps(cfg))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["workloads"][0]["chips"] = 4
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    if mode == "no_exchange":
        plant_no_exchange(cfg["hidden_size"])
    cell = spec.load_cell(small.CELL, root)
    r = harness.run_cell(cell, 11, 2.0, False, time.perf_counter(),
                         chip_check=False)
    print(json.dumps({"correct": r["correct"], "count": r["device"]["count"],
                      "checks": r["checks"]}))


if __name__ == "__main__":
    main()
