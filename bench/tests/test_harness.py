"""The harness end to end at CPU size through its test hook, pieces found
by name, the faults that must turn ``correct`` false, and the command's
refusal to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import calibrate, harness, spec
from bench.tests import small


@pytest.fixture()
def cell(tmp_path):
    return spec.load_cell(small.CELL, small.make_tree(tmp_path))


def run(cell, seed=2 ** 31 + 77, seconds=2.0, control=False):
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            chip_check=False, control=control)


def test_cpu_run_is_correct_and_reports_no_device_metrics(cell):
    r = run(cell)
    assert r["correct"] is True
    assert r["attempted"] == 8 and r["failed"] == 0
    assert r["metrics"] == {}                   # a CPU is not the device
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"widest_logit_gap", "requests_failed",
                                "window_compiles"}
    assert r["checks"]["window_compiles"]["value"] == 0


def test_token_altered_where_produced_fails(cell, monkeypatch):
    from repro.runtime import sampling
    real = sampling.sample_slots

    def altered(logits, *a, **kw):
        tok = real(logits, *a, **kw)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(sampling, "sample_slots", altered)
    r = run(cell)
    assert r["correct"] is False
    assert r["checks"]["widest_logit_gap"]["value"] > \
        r["checks"]["widest_logit_gap"]["limit"]


def test_step_that_returns_its_state_unchanged_fails(cell, monkeypatch):
    from repro.models import lm
    real = lm.lm_decode_step

    def frozen(params, cfg, token, position, cache, **kw):
        logits, _ = real(params, cfg, token, position, cache, **kw)
        return logits, cache                    # the arena never written
    monkeypatch.setattr(lm, "lm_decode_step", frozen)
    r = run(cell)
    assert r["correct"] is False


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_in_the_programs_place_reads_not_correct(cell, seed):
    """The float8 control, judged in the served tokens' place by the
    run's own comparison, turns ``correct`` false, while the same run's
    program reads far under the limit (the chip readings that set the
    cell's limit are in its cell file and PERF.md)."""
    r = run(cell, seed=seed, control=True)
    limit = r["checks"]["widest_logit_gap"]["limit"]
    assert r["correct"] is False
    assert r["checks"]["widest_logit_gap"]["value"] == \
        r["readings"]["control_widest_gap"] > limit
    assert r["readings"]["program_widest_gap"] < limit / 4
    assert list(r)[-1] == "checks"


def test_calibration_reads_both_through_the_run(cell):
    got = list(calibrate.readings(cell, [6], 2.0, chip_check=False))
    assert got[0]["correct"] is False
    assert got[0]["control_widest_gap"] > got[0]["limit"] > \
        got[0]["program_widest_gap"]


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = small.make_tree(tmp_path)
    (root / "metrics" / "served_requests.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "configs" / "small.json").read_text())
    (root / "configs" / "other.json").write_text(json.dumps(
        dict(cfg, name="other", num_hidden_layers=1)))
    mix = json.loads((root / "traffic" / "tiny.json").read_text())
    (root / "traffic" / "burst.json").write_text(json.dumps(
        dict(mix, greedy_share=1.0)))
    (root / "cells" / "other.burst.json").write_text(json.dumps(
        {"rate_per_s": 3.0, "check": {"sample": 1,
                                      "widest_logit_gap": 0.5}}))
    bench["configs"].append({"name": "other", "source": "test",
                             "file": "bench/configs/other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other.burst", "config": "other",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "served_requests", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "test", "moves": "ttft_p95_ms",
                               "workloads": ["other.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.load_cell("other.burst", root)
    assert c.config["num_hidden_layers"] == 1
    assert c.traffic["greedy_share"] == 1.0
    assert c.load["rate_per_s"] == 3.0
    assert [m["name"] for m in c.per_layer] == ["served_requests"]
    r = harness.run_cell(c, 1, 1.0, False, time.perf_counter(),
                         chip_check=False)
    assert r["correct"] and r["attempted"] == 3
    assert spec.reader(root, "served_requests")(
        type("R", (), {"requests": [0, 1, 2]})()) == 3


def test_command_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "'cpu'" in p.stderr


def test_every_workload_resolves(tmp_path):
    bench = json.loads((spec.BENCH_DIR.parent / "BENCHMARK.json")
                       .read_text())
    for w in bench["workloads"]:
        c = spec.load_cell(w["name"])
        assert c.load["rate_per_s"] > 0
        for m in c.end_to_end + c.per_layer:
            assert callable(spec.reader(c.root, m["name"]))
        assert any(m["name"] == "setup_s" for m in c.end_to_end)
        assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("no_exchange", False)])
def test_exchange_between_chips_left_out_fails(tmp_path, mode, correct):
    """The tensor-parallel cell's fault: on four virtual devices (its own
    process, so the device count can be forced), a run whose row-parallel
    projections skip the sum across chips reads not correct; the sound
    run reads correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "tests" / "tp_fault_run.py"),
         str(tmp_path), mode], capture_output=True, text=True, env=env,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["count"] == 4
    assert r["correct"] is correct, r
