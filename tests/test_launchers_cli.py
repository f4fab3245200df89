"""Launcher CLI smoke tests: the train/serve entrypoints run end-to-end
on reduced configs (subprocess — the real user-facing path)."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def _run(args, cache_dir, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # keep the launcher's persistent compile cache out of the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir / "jax_cache")
    return subprocess.run([sys.executable, "-m"] + args, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_train_cli_smoke(tmp_path):
    p = _run(["repro.launch.train", "--arch", "internlm2-1.8b", "--smoke",
              "--steps", "6", "--batch", "2", "--seq", "64",
              "--ckpt-dir", str(tmp_path), "--save-every", "3"], tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "loss" in p.stdout
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


@pytest.mark.slow
def test_serve_cli_smoke_with_a3(tmp_path):
    p = _run(["repro.launch.serve", "--arch", "phi4-mini-3.8b", "--smoke",
              "--requests", "2", "--prompt-len", "12", "--max-new", "4",
              "--max-len", "64", "--a3", "conservative"], tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "requests=2/2" in p.stdout


@pytest.mark.slow
def test_serve_cli_checkpoint_then_restore(tmp_path):
    """--l2-bytes / --checkpoint-dir / --restore: a run checkpoints at
    exit, and a second invocation restores the durable state (served
    results, trie, L2 tier) instead of starting cold."""
    ck = str(tmp_path / "ckpt")
    p = _run(["repro.launch.serve", "--arch", "phi4-mini-3.8b", "--smoke",
              "--requests", "2", "--prompt-len", "12", "--max-new", "4",
              "--max-len", "64", "--cache-pages", "8", "--page-size", "8",
              "--l2-bytes", str(1 << 24), "--checkpoint-dir", ck], tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "requests=2/2" in p.stdout
    assert "checkpointed engine" in p.stdout
    assert os.path.isdir(ck)
    p2 = _run(["repro.launch.serve", "--arch", "phi4-mini-3.8b", "--smoke",
               "--requests", "1", "--prompt-len", "12", "--max-new", "4",
               "--max-len", "64",
               "--checkpoint-dir", ck, "--restore"], tmp_path)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "restored engine" in p2.stdout
    assert "requests=1/1" in p2.stdout


@pytest.mark.slow
def test_dryrun_cli_list(tmp_path):
    p = _run(["repro.launch.dryrun", "--list"], tmp_path, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "grok-1-314b" in p.stdout and "long_500k" in p.stdout

@pytest.mark.slow
def test_serve_cli_telemetry_artifacts(tmp_path):
    """--stats-json (versioned v2 schema + config echo + metrics dump),
    --metrics-json, and --trace-out all land as valid JSON from one
    telemetered A^3 run."""
    import json
    stats = str(tmp_path / "stats.json")
    metrics = str(tmp_path / "metrics.json")
    trace = str(tmp_path / "trace.json")
    p = _run(["repro.launch.serve", "--arch", "phi4-mini-3.8b", "--smoke",
              "--requests", "2", "--prompt-len", "12", "--max-new", "4",
              "--max-len", "64", "--a3", "conservative",
              "--decode-block", "2", "--telemetry-every", "1",
              "--stats-json", stats, "--metrics-json", metrics,
              "--trace-out", trace], tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "requests=2/2" in p.stdout
    with open(stats) as f:
        snap = json.load(f)
    assert snap["schema"] == "a3-serve-stats/v2"
    assert snap["config"]["a3"] == "conservative"
    assert snap["config"]["serve"]["telemetry"] is True
    assert snap["stats"]["finished"] == 2
    # --metrics-json implies --telemetry, so the dump is present twice
    assert snap["metrics"]["schema"] == "a3-serve-metrics/v1"
    with open(metrics) as f:
        m = json.load(f)
    assert m["counters"]["serve_a3_probe_dispatches"] >= 1
    assert m["counters"]["serve_finished"] == 2
    with open(trace) as f:
        tr = json.load(f)
    assert tr["otherData"]["schema"] == "a3-serve-trace/v1"
    assert any(e["name"] == "terminal" for e in tr["traceEvents"])
