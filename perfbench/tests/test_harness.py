"""A whole run of the harness on the CPU at a tiny size, with the look for
a chip skipped: a sound run is correct, and each fault that a served cell
can have, planted in the timed path, makes ``correct`` false. Also the
control and the faults of host sampling put in the program's place: the
predicate that decides ``correct`` turns each down."""
import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SEED = 2**33 + 11


@pytest.fixture(autouse=True)
def tiny_sizes(monkeypatch):
    """The tiny cell's warm-in and the size of its check."""
    from perfbench import harness

    monkeypatch.setattr(harness, "WARM_IN_S", 0.5)
    monkeypatch.setattr(harness, "SAMPLE_TOKENS", 20)


def tiny_cell():
    from perfbench import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    load = {"rate_req_s": 30.0, "backlog": 4,
            "check": {"max_logit_gap": 0.05, "max_sampled_z": 6.0}}
    return harness.Cell(
        name="tiny", chips=1,
        config=json.loads((HERE / "tiny_config.json").read_text()),
        mix=json.loads((HERE / "tiny_mix.json").read_text()), load=load,
        end_to_end=harness._for_cell(bench["end_to_end"], "qwen2.5-3b.gen"),
        per_layer=[])


@pytest.mark.parametrize("fault,want", [
    (None, True), ("token_altered", False), ("state_unchanged", False),
    ("half_batch_left_out", False), ("sampled_greedy", False),
    ("sampled_wrong_row", False), ("temperature_skipped", False)])
def test_correct_only_when_the_timed_path_is_sound(fault, want):
    from perfbench import faults, harness

    r = harness.run(tiny_cell(), SEED, 2.0, False, time.perf_counter(),
                    require_chip=False,
                    fault=faults.ALL[fault] if fault else None)
    assert r["correct"] is want, r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) >= {"setup_s", "itl_mean_ms", "itl_p95_ms"}
    if want:
        assert r["checks"]["sampled_tokens"]["value"] >= 20
        assert r["checks"]["greedy_tokens"]["value"] >= 20


def test_control_and_sampling_faults_in_the_programs_place_fail():
    from perfbench import control

    (r,) = control.readings(tiny_cell(), [SEED], 2.0, require_chip=False)
    assert r["program"]["correct"] and r["program"]["malformed_requests"] == 0
    for name in ("fp8", "greedy", "temperature_1", "wrong_row"):
        assert not r[name]["correct"], (name, r[name])
    assert r["fp8"]["max_logit_gap"] > 0.05 >= r["program"]["max_logit_gap"]
    # the faults of host sampling leave greedy requests as served
    assert r["greedy"]["max_logit_gap"] == r["program"]["max_logit_gap"]


def test_no_chip_no_result():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         "qwen2.5-3b.gen", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
