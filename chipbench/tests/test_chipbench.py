"""CPU tests of the chip benchmark: the trace reduction, the traffic
generator, the readers' arithmetic, lookup by name (model kinds too), the
refusal off the TPU, the work counts, the dense kind's weights and
reference against pinned values, and whole tiny runs in which ``correct``
holds for the program and fails for the control and for a broken timed
path.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, traffic  # noqa: E402
from chipbench import tracereduce as TR  # noqa: E402
from chipbench.readout import Req, Run, Tick, itl_samples, percentile  # noqa: E402
from chipbench.references.dense_gqa import Work  # noqa: E402

import tiny  # noqa: E402

# limits of the small test cell, set between the program's readings (widest
# gap at most 0.015, mean at most 3.0e-4 over 6 runs) and the fp8
# control's (at least 0.19 and 0.014, 3 runs) at this size, on the CPU
TINY_LIMITS = {"logit_gap": 0.05, "mean_logit_gap": 0.002}


# -- trace reduction ----------------------------------------------------------

def _ev(name, start, dur, **args):
    return TR.Event(name, float(start), float(dur), args or None)


def test_trace_reduction_matches_programs_and_idle():
    """Ops are labelled by the program whose module holds them, read from
    the module's name; idle gaps by the harness's host span."""
    dev = "/device:TPU:0"
    modules = [_ev("jit_prefill_chunk(7)", 100, 50),
               _ev("jit_decode_block(9)", 160, 20),
               _ev("jit_convert", 185, 2), _ev("jit_decode_block(9)", 300, 20)]
    ops = [_ev("fusion.1", 100, 30), _ev("fusion.2", 130, 20),
           _ev("fusion.3", 160, 20), _ev("copy", 185, 2),
           _ev("fusion.3", 300, 20), _ev("late", 450, 100)]
    host = [_ev("chipbench.window", 90, 310), _ev("chipbench.step", 95, 100),
            _ev("chipbench.observe", 195, 10), _ev("chipbench.wait", 205, 90),
            _ev("chipbench.step", 295, 30)]
    tr = TR.reduce(TR.TraceEvents({dev: modules}, {dev: ops}, host))
    assert tr.programs == {
        "jit_prefill_chunk": {"device_s": pytest.approx(50e-9), "modules": 1,
                              "idle_s": 0.0},
        "jit_decode_block": {"device_s": pytest.approx(40e-9), "modules": 2,
                             "idle_s": 0.0},
        "jit_convert": {"device_s": pytest.approx(2e-9), "modules": 1,
                        "idle_s": 0.0}}
    assert tr.window_s == pytest.approx(310e-9)
    # busy: [100,150) + [160,180) + [185,187) + [300,320) in [90, 400)
    assert tr.busy_s == pytest.approx(92e-9)
    # each idle gap goes to the host span that holds its midpoint
    idle = dict(tr.idle_gaps)
    assert idle["step"] == pytest.approx(25e-9)       # 90-100, 150-160, 180-185
    assert idle["wait"] == pytest.approx(113e-9)      # 187-300
    assert idle["other"] == pytest.approx(80e-9)      # 320-400
    assert "observe" not in idle
    ops_by = dict(tr.device_ops)
    assert ops_by["jit_decode_block:fusion.3"] == pytest.approx(40e-9)
    assert ops_by["jit_prefill_chunk:fusion.1"] == pytest.approx(30e-9)
    assert ops_by["jit_convert:copy"] == pytest.approx(2e-9)
    assert not any(k.startswith("other:") for k in ops_by)
    assert "late" not in tr.ops                                  # after the window


def test_trace_reduction_refuses_to_guess_on_mismatch():
    """An op that lies in no module event is labelled ``other``, never
    charged to the program that ran before or after it; a module name
    that is not a program name's characters stays whole."""
    dev = "/device:TPU:0"
    ev = TR.TraceEvents({dev: [_ev("jit_decode_block(3)", 10, 5),
                               _ev("jit_decode_block(3)", 40, 5)]},
                        {dev: [_ev("fusion", 10, 5), _ev("stray", 20, 5),
                               _ev("fusion", 40, 5)]},
                        [_ev("chipbench.window", 0, 100)])
    tr = TR.reduce(ev)
    assert dict(tr.device_ops) == {"jit_decode_block:fusion": pytest.approx(10e-9),
                                   "other:stray": pytest.approx(5e-9)}
    assert tr.programs["jit_decode_block"]["device_s"] == pytest.approx(10e-9)
    assert tr.busy_s == pytest.approx(15e-9)
    assert TR.program_of("jit_prefill_chunk_nosort(12)") == \
        "jit_prefill_chunk_nosort"
    assert TR.program_of("(odd)") == "(odd)"


def test_union_and_gaps():
    u = TR.union([(5, 10), (0, 3), (2, 4), (9, 12)], 1, 11)
    assert u == [(1, 4), (5, 11)]
    assert TR.gaps(u, 0, 20) == [(0, 1), (4, 5), (11, 20)]


def test_measure_of_intervals_inside_a_span_matches_a_plain_sum():
    rng = np.random.default_rng(3)
    edges = np.sort(rng.choice(1000, size=200, replace=False)).astype(float)
    intervals = list(zip(edges[::2], edges[1::2]))
    m = TR.Measure(intervals)
    assert m.total() == pytest.approx(sum(e - s for s, e in intervals))
    for a, b in rng.uniform(-50, 1050, size=(300, 2)):
        a, b = min(a, b), max(a, b)
        plain = sum(max(0.0, min(e, b) - max(s, a)) for s, e in intervals)
        assert m.within(a, b) == pytest.approx(plain, abs=1e-9)
    assert m.within(5, 5) == 0 and TR.Measure([]).within(0, 10) == 0


# -- traffic -------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat-poisson"])
def test_generator_is_deterministic_by_seed(mix):
    m = traffic.load_mix(mix)
    big = 2 ** 31 + 12345
    a = traffic.generate(m, big, 10, 1000)
    b = traffic.generate(m, big, 10, 1000)
    c = traffic.generate(m, big + 1, 10, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: another order and other tokens, the same sizes
    assert sorted((len(r.prompt), r.max_new) for r in a) == \
        sorted((len(r.prompt), r.max_new) for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    lo, hi = m["prompt"]["min"], m["prompt"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(len(r.prompt) + r.max_new - 1 <= m["max_len"] for r in a)
    if m["loop"] == "open":
        gaps = np.diff([0.0] + [r.due for r in a])
        assert sorted(gaps) == pytest.approx(
            sorted(np.diff([0.0] + [r.due for r in c])))
        assert np.mean(gaps) == pytest.approx(1 / m["rate_per_s"], rel=0.15)


def test_lognormal_quantiles_have_the_stated_median():
    spec = {"dist": "lognormal", "median": 320, "sigma": 0.7,
            "min": 32, "max": 768}
    x = traffic.quantile_lengths(spec, 1001)
    assert np.median(x) == 320 and x.min() >= 32 and x.max() == 768


# -- readers -------------------------------------------------------------------

def _run(requests, ticks=(), loop="open"):
    return Run(t0=10.0, t1=20.0, setup_s=3.0, loop=loop,
               requests=list(requests), ticks=list(ticks),
               work=Work(json.loads((ROOT / "chipbench/configs/"
                                     "phi4-mini-3.8b.json").read_text()), 1024),
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_percentile_is_over_all_samples():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 1001):
        v = rng.exponential(size=n).tolist()
        for q in (50, 90, 99):
            assert percentile(v, q) == pytest.approx(np.percentile(v, q))
    assert percentile([], 50) is None


def test_rates_and_tails_use_the_whole_window():
    reqs = [Req(prompt_len=10, max_new=4, due=9.0, sent=9.5,
                token_times=[9.9, 10.5, 11.0, 21.0]),
            Req(prompt_len=10, max_new=3, due=12.0, sent=12.0,
                token_times=[12.4, 12.9, 19.9])]
    run = _run(reqs)
    out = harness.load_reader("output_tok_s")(run)
    assert out == pytest.approx(5 / 10.0)      # 5 tokens inside [10, 20]
    assert sorted(itl_samples(run)) == pytest.approx([0.5, 0.5, 0.6, 7.0])
    assert harness.load_reader("itl_p99_ms")(run) == pytest.approx(
        np.percentile([0.6, 0.5, 0.5, 7.0], 99) * 1e3)
    # only the request whose first token arrived in the window, from due
    assert harness.load_reader("ttft_p50_ms")(run) == pytest.approx(400.0)
    closed = _run(reqs, loop="closed")
    assert harness.load_reader("ttft_p90_ms")(closed) == pytest.approx(400.0)


def test_scheduler_and_device_readers():
    ticks = [Tick(10.0, 10.1, 1, 1, [(0, 300, True)], 1, 1, [301]),
             Tick(10.1, 10.2, 0, 1, [], 1, 1, [302, 40]),
             Tick(10.2, 10.3, 0, 1, [], 1, 1, [303, 41])]
    run = _run([], ticks)
    assert harness.load_reader("decode_occupancy")(run) == pytest.approx(5 / 3)
    dev = "/device:TPU:0"
    mods = [_ev("jit_prefill_chunk(2)", 0, 2e8),
            _ev("jit_decode_block(5)", 2e8, 2e7),
            _ev("jit_decode_block(5)", 2.2e8, 2e7),
            _ev("jit_decode_block(5)", 2.4e8, 2e7)]
    host = [_ev("chipbench.window", 0, 5e8),
            _ev("serve.dispatch.prefill", 0, 1e3, tokens=300, positions=512),
            *[_ev("serve.dispatch.decode", t, 1e3, steps=1)
              for t in (2e8, 2.2e8, 2.4e8)],
            _ev("serve.tick", 0, 4e8)]
    run.trace = TR.reduce(TR.TraceEvents({dev: mods}, {}, host))
    run.t0 = 10.0
    assert harness.load_reader("decode_program_ms")(run) == pytest.approx(20.0)
    assert harness.load_reader("prefill_program_ms_per_ktok")(run) == \
        pytest.approx(200.0 / 0.3)
    assert harness.load_reader("device_idle_share")(run) == \
        pytest.approx(100 * (1 - 0.26 / 0.5))
    assert harness.load_reader("step_idle_share")(run) == \
        pytest.approx(100 * 0.14 / 0.5)
    roof = harness.load_reader("decode_roofline")(run)
    w = run.work
    least = sum(w.decode_step_least_s(1, t.decode_keys, run.peaks)
                for t in ticks)
    assert roof == pytest.approx(100 * least / 0.06)
    assert 0 < roof <= 100
    mfu = harness.load_reader("serve_mfu")(run)
    flops = w.prefill_flops(0, 300, True) + sum(
        w.decode_lane_flops(k) for t in ticks for k in t.decode_keys)
    assert mfu == pytest.approx(100 * flops / 0.5 / 197e12)
    run.counters = {"prefill_tokens": 300, "prefill_positions": 512}
    assert harness.load_reader("prefill_padding_share")(run) == \
        pytest.approx(100 * (1 - 300 / 512))
    run.trace = None
    for name in ("decode_program_ms", "decode_roofline", "step_idle_share"):
        assert harness.load_reader(name)(run) is None


ROUTER_READER = '''"""Made-up reader of names the harness has never heard of."""


def read(run):
    sec = run.program_seconds("jit_route_tokens")
    spans = run.trace.spans.get("serve.dispatch.route")
    if sec is None or not spans:
        return None
    return (sec * 1e3 / spans["args"]["experts"]
            + run.counters["tokens_routed"] / spans["count"])
'''


def test_a_reader_in_a_new_file_reads_new_program_span_and_counter(
        tmp_path, monkeypatch):
    """A new model's program, span and counter reach a reader that is one
    new file under metrics/, with no edit to the harness."""
    (tmp_path / "route_ms_per_expert.py").write_text(ROUTER_READER)
    monkeypatch.setattr(harness, "METRICS", tmp_path)
    dev = "/device:TPU:0"
    mods = [_ev("jit_route_tokens(4)", 10, 30), _ev("jit_route_tokens(4)", 50, 10)]
    host = [_ev("chipbench.window", 0, 100),
            _ev("serve.dispatch.route", 9, 1, experts=8, lanes=4),
            _ev("serve.dispatch.route", 49, 1, experts=4, lanes=4)]
    run = _run([])
    run.trace = TR.reduce(TR.TraceEvents({dev: mods}, {}, host))
    run.counters = {"tokens_routed": 64}
    read = harness.load_reader("route_ms_per_expert")
    assert read(run) == pytest.approx(40e-9 * 1e3 / 12 + 64 / 2)


# -- lookup by name ------------------------------------------------------------

def test_cells_configs_traffic_and_metrics_are_found_by_name():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.find_cell(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix == traffic.load_mix(w["traffic"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert (harness.REFERENCES
                / f"{cell.config['reference']}.py").is_file()
        for name in ("program_params", "Reference", "Work"):
            assert hasattr(cell.kind, name)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
    with pytest.raises(KeyError):
        harness.find_cell(bench, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no-such-mix")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric")


def test_benchmark_file_keeps_to_its_shape():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_unknown_model_kind_is_refused_before_any_weights(tmp_path):
    bench = harness.load_benchmark()
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg["reference"] = "no_such_kind"
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bench = dict(bench, configs=[dict(bench["configs"][0],
                                      file=str(tmp_path / "cfg.json"))])
    w = bench["workloads"][0]
    with pytest.raises(FileNotFoundError, match="references/no_such_kind.py"):
        harness.find_cell(bench, w["name"])
    with pytest.raises(FileNotFoundError, match="references/no_such_kind.py"):
        harness.load_kind("no_such_kind")


# -- refusal off the TPU -------------------------------------------------------

def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "phi4-chat-poisson", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "phi4-chat-poisson", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not in this checkout" in p.stderr


# -- work counts ---------------------------------------------------------------

def _work(name, ring):
    return Work(json.loads((ROOT / f"chipbench/configs/{name}.json")
                           .read_text()), ring)


def test_work_counts_match_hand_numbers():
    phi = _work("phi4-mini-3.8b", 1024)
    assert phi.params() == 4_450_618_368
    assert phi.weight_bytes() == 8_901_236_736
    assert phi.kv_bytes_per_token() == 131_072          # 32 x 2 x 8 x 128 x 2
    assert phi.step_weight_bytes() == 8_901_236_736 - 200064 * 3072 * 2
    assert phi.decode_lane_bytes(1000) == 1000 * 131_072
    # 2 x params per token, and causal attention keys 1 + 2 + ... + n
    assert phi.prefill_flops(0, 2, False) == (
        2 * 32 * phi.layer_params() * 2 + 32 * 4 * 24 * 128 * 3)


# -- the dense kind against the parent ------------------------------------------

# Values computed at commit 58f588ecf0a4b7f87a264875c49ef5468328d35d, where
# weights.py, reference.py and workcount.py held the dense GQA layer, for
# the tiny configuration and seed 2**33 + 5: the sha256 of the program's
# parameter leaves (sorted by path, each path then its bytes), and the
# reference's served and fp8 control gaps on the two sequences below.
PARENT_PARAMS_SHA256 = (
    "569b818b43e7ecb4cf335be467760a5c1e184bc1f4058c7bdfbdea69e5c27db3")
PARENT_SERVED_GAPS = [
    [1.3762218952178955, 5.728619575500488, 1.3330061435699463,
     3.188261032104492, 2.2053580284118652, 3.062866449356079],
    [5.9397101402282715, 4.235085487365723, 2.4417953491210938,
     3.624140501022339, 4.349161624908447]]
PARENT_CONTROL_GAPS = [
    [0.034507036209106445, 0.0, 0.0, 0.0, 0.0, 0.0],
    [0.0, 0.024850130081176758, 0.0, 0.0, 0.0]]


def _pinned_seqs():
    from chipbench.reference import Sequence
    return [Sequence(np.arange(40, dtype=np.int32) * 37 % 8192,
                     np.array([5, 901, 77, 4000, 12, 8191], np.int32)),
            Sequence(np.arange(70, dtype=np.int32) * 113 % 8192,
                     np.array([3, 2, 1, 7000, 555], np.int32))]


def test_dense_kind_weights_and_reference_match_the_parent():
    import jax
    from chipbench.references import dense_gqa
    cfg, seed = tiny.tiny_config(), 2 ** 33 + 5
    leaves = jax.tree_util.tree_flatten_with_path(
        dense_gqa.program_params(cfg, seed))[0]
    h = hashlib.sha256()
    for path, leaf in sorted(leaves,
                             key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PARENT_PARAMS_SHA256
    served, ctl = dense_gqa.Reference(cfg, 256, seed).gaps(_pinned_seqs(),
                                                           control=True)
    want = PARENT_SERVED_GAPS + PARENT_CONTROL_GAPS
    for got, w in zip(served + ctl, want):
        np.testing.assert_allclose(got, w, rtol=0, atol=1e-6)


TWO_GROUPS_KIND = '''"""Toy kind: dense GQA layers, the first two of
them in a group of their own whose FFN is WIDE times as wide."""
from chipbench.references import dense_gqa

WIDE = {wide}


class Reference(dense_gqa.Reference):
    def layer_group(self, layer):
        return "lead" if layer < 2 else "rest"

    def layer_weights(self, layer, group):
        cfg = self.cfg
        if group == "lead":
            cfg = dict(cfg, intermediate_size=WIDE * cfg["intermediate_size"])
        return dense_gqa.layer_weights(cfg, self.key, layer)
'''


@pytest.mark.parametrize("wide", [1, 2])
def test_a_kind_with_two_layer_groups_runs_to_gaps(tmp_path, monkeypatch,
                                                   wide):
    """A kind whose layers differ in their weights' shapes is one new file
    under references/: each group compiles its own weights program. With
    the groups alike (``wide`` 1) it is the dense kind to the pinned
    gaps."""
    from chipbench import reference
    (tmp_path / "two_groups.py").write_text(TWO_GROUPS_KIND.format(wide=wide))
    monkeypatch.setattr(harness, "REFERENCES", tmp_path)
    kind = harness.load_kind("two_groups")
    cfg, seed = tiny.tiny_config(), 2 ** 33 + 5
    ref = kind.Reference(cfg, 256, seed)
    shapes = [ref.layer_weights(i, ref.layer_group(i))["w_up"].shape
              for i in range(cfg["num_hidden_layers"])]
    assert shapes == [(256, 512 * wide)] * 2 + [(256, 512)] * 2
    before = reference.Reference._layer._cache_size()
    served, ctl = ref.gaps(_pinned_seqs(), control=True)
    # two groups, each as itself and as the fp8 control
    assert reference.Reference._layer._cache_size() - before == 4
    assert [len(g) for g in served] == [6, 5] == [len(g) for g in ctl]
    assert all(np.isfinite(g).all() and (g >= 0).all() for g in served + ctl)
    pinned = PARENT_SERVED_GAPS + PARENT_CONTROL_GAPS
    diff = max(np.abs(g - w).max() for g, w in zip(served + ctl, pinned))
    if wide == 1:
        assert diff <= 1e-6
    else:
        assert diff > 1e-3


# -- whole tiny runs on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def tiny_arch():
    tiny.register_tiny_arch()


def _tiny(loop="closed"):
    t = time.perf_counter()
    return harness.run_cell(tiny.tiny_cell(loop, TINY_LIMITS), 2 ** 33 + 5,
                            3.0, False, t, control=True)


def test_tiny_run_is_correct(tiny_arch):
    out = _tiny("open")
    assert out.correct, out.compared
    assert out.failed == 0 and out.attempted > 0
    assert set(out.metrics) >= {"output_tok_s", "itl_p99_ms", "setup_s"}
    assert out.diagnostics["window_compiles"]["backend_compiles"] == 0
    for name, limit in TINY_LIMITS.items():
        assert 0 <= out.compared[name]["value"] <= limit


def test_a_copied_kind_module_runs_the_tiny_cell(tiny_arch, tmp_path,
                                                monkeypatch):
    """A new model kind is one file under references/ and nothing else."""
    shutil.copy(harness.REFERENCES / "dense_gqa.py",
                tmp_path / "dense_gqa_copy.py")
    monkeypatch.setattr(harness, "REFERENCES", tmp_path)
    cell = tiny.tiny_cell("closed", TINY_LIMITS, reference="dense_gqa_copy")
    assert cell.kind.__file__ == str(tmp_path / "dense_gqa_copy.py")
    out = harness.run_cell(cell, 2 ** 33 + 8, 3.0, False, time.perf_counter())
    assert out.correct, out.compared
    assert out.failed == 0 and out.attempted > 0


def test_cell_without_limits_is_not_correct(tiny_arch):
    t = time.perf_counter()
    out = harness.run_cell(tiny.tiny_cell("closed", None), 2 ** 33 + 6, 3.0,
                           False, t)
    assert not out.correct and out.compared == {}
    assert set(out.diagnostics["gaps"]) == set(TINY_LIMITS)


def test_control_fails(tiny_arch):
    d = _tiny().diagnostics
    assert any(d["control_gaps"][name] > limit
               for name, limit in TINY_LIMITS.items())


def _broken_decode_state(decoder):
    real = decoder.decode_step

    def step(params, cfg, cache, *a, **kw):
        out = real(params, cfg, cache, *a, **kw)
        return (out[0], cache) + tuple(out[2:])
    return step


def _altered_token(decoder):
    real = decoder.sample_logits

    def sample(logits, **kw):
        return (real(logits, **kw) + 1) % logits.shape[-1]
    return sample


@pytest.mark.parametrize("fault", ["decode_state_unchanged",
                                   "token_altered"])
def test_broken_timed_path_is_not_correct(tiny_arch, monkeypatch, fault):
    from repro.models import decoder
    if fault == "decode_state_unchanged":
        monkeypatch.setattr(decoder, "decode_step",
                            _broken_decode_state(decoder))
    else:
        monkeypatch.setattr(decoder, "sample_logits", _altered_token(decoder))
    out = _tiny()
    assert not out.correct
    assert out.compared["logit_gap"]["value"] > TINY_LIMITS["logit_gap"]
