"""A benchmark root at smoke size for CPU rehearsals: the real cells'
configuration and traffic files with the program's smoke sizes and a
short, light load, written under a temporary directory."""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMOKE_SIZES = {
    "starcoder2_3b": {"hidden_size": 64, "num_hidden_layers": 2,
                      "num_attention_heads": 4, "num_key_value_heads": 1,
                      "head_dim": 16, "intermediate_size": 128,
                      "vocab_size": 512},
    "mamba2_370m": {"d_model": 64, "n_layer": 2, "vocab_size": 512,
                    "d_state": 16, "headdim": 16},
}
SMOKE_SERVING = {"smoke": True, "slots": 4, "max_seq": 256, "seg_len": 4}
# At smoke size on the CPU the program's widest gap reads 0.002-0.006
# (bf16 rounding at 2 layers of width 64); the cells' own limits are set
# for their published sizes on the chip.
SMOKE_LIMIT = 0.05
SMOKE_MIX = {"rate_per_s": 20.0, "lead_in_s": 0.2,
             "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 8,
                               "max": 60},
             "output_tokens": {"median": 6, "sigma": 0.5, "min": 2,
                               "max": 16}}


def smoke_root(tmp, names=None, rename=None):
    """Write a root under `tmp` holding a BENCHMARK.json with the given
    workloads (default: all) at smoke size; `rename` maps an existing
    workload to (new workload, new config, new mix) names, whose files
    are written under the new names only.  Returns (root, workload
    names)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(tmp, "bench", sub), exist_ok=True)
    for m in os.listdir(os.path.join(ROOT, "bench", "metrics")):
        shutil.copy(os.path.join(ROOT, "bench", "metrics", m),
                    os.path.join(tmp, "bench", "metrics", m))
    cells = [w for w in spec["workloads"]
             if names is None or w["name"] in names]
    for w in cells:
        conf = next(c for c in spec["configs"] if c["name"] == w["config"])
        with open(os.path.join(ROOT, conf["file"])) as f:
            cfg = json.load(f)
        cfg.update(SMOKE_SIZES[w["config"]])
        cfg["serving"] = dict(SMOKE_SERVING)
        cfg["check"] = dict(cfg["check"], widest_gap_limit=SMOKE_LIMIT)
        with open(os.path.join(ROOT, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        mix.update(SMOKE_MIX)
        mix["sampling"] = dict(mix["sampling"],
                               greedy_every=min(
                                   2, mix["sampling"]["greedy_every"]))
        cname, mname = w["config"], w["traffic"]
        if rename and w["name"] in rename:
            new, cname, mname = rename[w["name"]]
            cfg["name"] = cname
            conf = dict(conf, name=cname)
            spec["configs"].append(conf)
            w.update(name=new, config=cname, traffic=mname)
        conf["file"] = f"bench/configs/{cname}.json"
        with open(os.path.join(tmp, conf["file"]), "w") as f:
            json.dump(cfg, f)
        with open(os.path.join(tmp, "bench", "traffic", mname + ".json"),
                  "w") as f:
            json.dump(mix, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp, [w["name"] for w in cells]
