"""A benchmark tree at CPU size for the tests: the real configuration
files with their widths shrunk, small traffic, and the real readers and
architecture module, in a temporary directory beside its own
``BENCHMARK.json``."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import spec

SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256}
ENGINE = {"num_slots": 4, "chunk_size": 4, "block_size": 4, "max_seq": 64,
          "num_blocks": 48, "mesh": None, "dtype": "bfloat16"}
MIX = {"prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                         "min": 4, "max": 40},
       "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                         "min": 2, "max": 16},
       "sampling": {"temperature": 0.7, "top_p": 0.95},
       "greedy_share": 0.5}
CELL = "small.chat"
# The widest logit gap at this size, set from CPU readings as the cell's
# limit is from the chip's (seeds 3-8, two-second windows): the program
# reads at most 0.0006, the float8 control at least 0.011.
LIMIT = 0.005


def make_tree(tmp: Path, rate: float = 4.0, limit: float = LIMIT) -> Path:
    """A bench root under ``tmp`` holding one cell, ``small.chat``;
    returns the root (its ``BENCHMARK.json`` sits in ``tmp``)."""
    src = spec.BENCH_DIR
    root = tmp / "bench"
    (root / "configs").mkdir(parents=True)
    (root / "traffic").mkdir()
    (root / "cells").mkdir()
    shutil.copytree(src / "metrics", root / "metrics")
    shutil.copy(src / "configs" / "qwen3.py", root / "configs")
    shutil.copy(src / "peaks.json", root)
    cfg = json.loads((src / "configs" / "qwen3-1.7b.json").read_text())
    cfg.update(SMALL, name="small", engine=ENGINE)
    (root / "configs" / "small.json").write_text(json.dumps(cfg))
    (root / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    (root / "cells" / f"{CELL}.json").write_text(json.dumps(
        {"rate_per_s": rate, "check": {"sample": 2,
                                       "widest_logit_gap": limit}}))
    bench = json.loads((src.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "small", "source": "test",
                         "file": "bench/configs/small.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "small",
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if m["name"].endswith(".chat") or \
                "ttft" in m["name"] or "itl" in m["name"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
