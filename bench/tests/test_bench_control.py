"""The correctness check's control at a size a test run can hold: the
plain reference computed in int8 (the precision below the bfloat16 the
configurations state), read at the positions and served tokens of the
program's own run, must read worse than the program on every seed, so
that a limit set between the two fails it.  The cells' own
readings, at their published sizes on the chip, are in PERF.md."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402
from bench import harness  # noqa: E402

# smoke widths; depth where the int8 control's error shows above the
# program's bfloat16 rounding (at 2 layers of width 64 the SSD stack
# reads the same either way)
DEPTH = {"starcoder2_3b.code_fim": 8, "mamba2_370m.chat_burst": 16}


@pytest.mark.parametrize("workload", sorted(DEPTH))
def test_int8_control_reads_worse_than_the_program(tmp_path, workload):
    root, _ = rehearsal.smoke_root(str(tmp_path), [workload])
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    conf = next(c for c in spec["configs"]
                if c["name"] == workload.split(".")[0])
    path = os.path.join(root, conf["file"])
    cfg = json.load(open(path))
    cfg["serving"]["n_layers"] = DEPTH[workload]
    depth_key = "n_layer" if "n_layer" in cfg else "num_hidden_layers"
    cfg[depth_key] = DEPTH[workload]
    json.dump(cfg, open(path, "w"))
    sess = harness.set_up(root, workload, 1, require_tpu=False,
                          use_cache=False)
    program, control = [], []
    for seed in (1, 2, 3):
        harness.reseed(sess, seed)
        win = harness.measure(sess, seed, 1.0)
        sample = harness.check_sample(
            win.reqs, harness.finished(sess.server, win.reqs), seed)
        gaps = harness.served_gaps(sess.cell, sess.server.params, sample,
                                   ("f32", "int8"))
        assert len(gaps["f32"]) >= 50
        program.append(float(gaps["f32"].max()))
        control.append(float(gaps["int8"].max()))
        sess.server.completed.clear()
    # a limit set between the program's highest reading and the
    # control's lowest fails the control on every seed
    limit = (max(program) + min(control)) / 2
    assert min(control) > limit >= max(program), (program, control)
