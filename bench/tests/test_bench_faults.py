"""The check must fail a run whose timed path is broken underneath: a
decode step that hands back its state unchanged, and a token altered
where it is produced.  Each fault is planted in the server the harness
builds, at smoke size on the CPU; everything else runs as in a real run
past the look for a chip."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402
from bench import harness  # noqa: E402

STEPS = ("segment_fn", "segment_plain_fn")


def state_unchanged(server):
    for name in STEPS:
        fn = getattr(server, name)

        def step(params, cache, state, fn=fn):
            kept = jax.tree.map(jnp.copy, cache)
            seg, emit, state, _ = fn(params, cache, state)
            return seg, emit, state, kept
        setattr(server, name, step)


def token_altered(server):
    vocab = server.cfg.vocab
    for name in STEPS:
        fn = getattr(server, name)

        def step(params, cache, state, fn=fn):
            seg, emit, state, cache = fn(params, cache, state)
            seg = seg.at[:, -1].set((seg[:, -1] + 1) % vocab)
            return seg, emit, state, cache
        setattr(server, name, step)


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
@pytest.mark.parametrize("workload", ["starcoder2_3b.code_fim",
                                      "mamba2_370m.chat_burst"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, workload,
                                          fault):
    root, _ = rehearsal.smoke_root(str(tmp_path), [workload])
    build = harness.build_server

    def broken(cell, seed):
        server = build(cell, seed)
        fault(server)
        return server
    monkeypatch.setattr(harness, "build_server", broken)
    out = harness.run_cell(root, workload, 2 ** 35 + 3, 1.0, False,
                           t_process=time.perf_counter(), require_tpu=False,
                           use_cache=False, log=lambda s: None)
    c = out["compared"]["widest_gap"]
    assert out["correct"] is False, c
    assert c["value"] > c["limit"]
