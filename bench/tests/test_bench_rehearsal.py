"""CPU rehearsal of every cell at smoke size through the harness:
generator -> BatchedServer -> metrics -> result line; a cell added as new
files under new names runs without an edit; the command refuses to run
without a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402
from bench import harness  # noqa: E402

SECONDS = 1.0


def run(root, name, trace=False, **kw):
    return harness.run_cell(root, name, 2 ** 33 + 11, SECONDS, trace,
                            t_process=time.perf_counter(),
                            require_tpu=False, use_cache=False,
                            log=lambda s: None, **kw)


@pytest.mark.parametrize("workload", ["starcoder2_3b.code_fim",
                                      "mamba2_370m.chat_burst"])
def test_cell_rehearsal(tmp_path, workload):
    root, _ = rehearsal.smoke_root(str(tmp_path), [workload])
    out = run(root, workload)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 10 and out["failed"] == 0
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for m in spec["end_to_end"]:
        if workload not in m.get("workloads", [workload]):
            assert m["name"] not in out["metrics"]
            continue
        assert out["metrics"][m["name"]]["value"] > 0, m["name"]
        assert out["metrics"][m["name"]]["unit"] == m["unit"]
    assert list(out)[-1] == "compared"
    assert out["compared"]["widest_gap"]["value"] <= \
        out["compared"]["widest_gap"]["limit"]
    assert out["device"]["platform"] == "cpu"


def test_new_cell_runs_from_new_files_alone(tmp_path):
    """A configuration, a mix and a per-layer metric added under new names
    are found by the harness with no edit to any file it already has."""
    root, _ = rehearsal.smoke_root(
        str(tmp_path), ["starcoder2_3b.code_fim"],
        rename={"starcoder2_3b.code_fim": ("tmp_model.tmp_mix", "tmp_model",
                                           "tmp_mix")})
    with open(os.path.join(root, "bench", "metrics", "tmp_tokens.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return float(run.counters['tokens_emitted'])\n")
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["per_layer"].append({
        "name": "tmp_tokens", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "output_tokens_per_s", "workloads": ["tmp_model.tmp_mix"]})
    json.dump(spec, open(path, "w"))
    assert sorted(os.listdir(os.path.join(root, "bench", "configs"))) \
        == ["tmp_model.json"]
    assert sorted(os.listdir(os.path.join(root, "bench", "traffic"))) \
        == ["tmp_mix.json"]
    out = run(root, "tmp_model.tmp_mix", trace=True)
    assert out["correct"] is True
    assert out["metrics"]["tmp_tokens"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "starcoder2_3b.code_fim", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_without_a_tpu():
    p = _cli(rehearsal.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(os.path.join(rehearsal.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(rehearsal.ROOT, "bench"),
                    os.path.join(tmp_path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout
