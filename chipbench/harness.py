"""Runs one benchmark cell once: set-up, the measured window, the readers,
and the comparison with the plain reference that decides ``correct``.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<mix>.json``, its model kind (weights, reference, work counts)
in ``references/<reference>.py``, each metric's reader in
``metrics/<metric>.py`` and the cell's limits in ``limits/<cell>.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import traffic as T
from chipbench.readout import Req, Run, Tick, percentile
from chipbench.workcount import peaks_for

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"
REFERENCES = BENCH / "references"
METRICS = BENCH / "metrics"
MAX_WARM_TICKS = 400


# ---------------------------------------------------------------------------
# lookup by name
# ---------------------------------------------------------------------------

def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                  # configs/<config>.json
    kind: ModuleType              # references/<config["reference"]>.py
    mix: dict                     # traffic/<mix>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Optional[dict]        # limits/<cell>.json, if set


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    limits_file = BENCH / "limits" / f"{name}.json"
    limits = (json.loads(limits_file.read_text())
              if limits_file.is_file() else None)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                kind=load_kind(config["reference"]),
                mix=T.load_mix(w["traffic"]),
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                limits=limits)


def _load(path: Path, what: str, package: str, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no {what} {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{package}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable[[Run], Optional[float]]:
    return _load(METRICS / f"{metric}.py", "reader for metric",
                 "metrics", metric).read


def load_kind(reference: str) -> ModuleType:
    """The model kind a configuration's ``"reference"`` names: its
    ``program_params``, ``Reference`` and ``Work``."""
    return _load(REFERENCES / f"{reference}.py", "model kind",
                 "references", reference)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def import_program():
    """The program's modules, from this checkout's ``src``."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro import config as rc
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import decoder
    from repro.serve import engine as eng
    return rc, enable_compile_cache, decoder, eng


class CompileMeter:
    """Tracing and backend compilations, their seconds, and the persistent
    cache's hits, counted by JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.traces, self.compiles, self.cache_hits = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("jaxpr_trace_duration"):
                self.traces += 1
            elif event.endswith("backend_compile_duration"):
                self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "traces": self.traces,
                "backend_compiles": self.compiles, "cache_hits": self.cache_hits}


def build_engine(cell: Cell, params, program):
    rc, _, decoder, eng = program
    if cell.config.get("a3"):
        raise ValueError("no reference for A^3 decoding: exact cells only")
    cfg = rc.get_arch(cell.config["arch"])
    serve = rc.ServeConfig(slots=int(cell.mix["slots"]),
                           max_len=int(cell.mix["max_len"]))
    return eng.ServeEngine.from_config(params, cfg, serve), cfg


def check_params_tree(params, cfg, decoder) -> None:
    """The benchmark's weights must have the program's tree, shapes and
    dtypes exactly."""
    import jax
    want = jax.eval_shape(lambda k: decoder.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------

class Client:
    """Drives one engine through ``submit`` and ``step`` and records what a
    client sees: when each request was due, sent and admitted, and when
    each of its tokens arrived."""

    def __init__(self, engine, eng_mod, mix: dict, reqs: List[T.Request],
                 annotate: Callable[[str], object]):
        self.engine, self.eng, self.mix = engine, eng_mod, mix
        self.pool = reqs
        self.next = 0
        self.origin = 0.0            # open loop: due times count from here
        self.live: Dict[int, Req] = {}
        self.done: List[Req] = []
        self.all: List[Req] = []
        self.ticks: List[Tick] = []
        self.annotate = annotate
        self.lateness: List[float] = []
        self.closed = mix["loop"] == "closed"

    def _submit(self, tr: T.Request, due: float) -> None:
        with self.annotate("submit"):
            now = time.perf_counter()
            uid = self.engine.submit(tr.prompt, max_new_tokens=tr.max_new)
        r = Req(prompt_len=len(tr.prompt), max_new=tr.max_new, due=due,
                sent=now, uid=uid, prompt=tr.prompt)
        self.lateness.append(now - due)
        self.live[uid] = r
        self.all.append(r)

    def _take(self) -> T.Request:
        if self.next >= len(self.pool):
            raise RuntimeError("traffic pool exhausted; raise its size")
        tr = self.pool[self.next]
        self.next += 1
        return tr

    def start_closed(self) -> None:
        for _ in range(int(self.mix["clients"])):
            self._submit(self._take(), time.perf_counter())

    def _send_due(self) -> None:
        now = time.perf_counter()
        while (self.next < len(self.pool)
               and self.origin + self.pool[self.next].due <= now):
            tr = self._take()
            self._submit(tr, self.origin + tr.due)

    def tick(self) -> Tick:
        eng, stats = self.engine, self.engine.stats
        before = {s.uid: s.cursor for s in eng.slots if s.active}
        s0 = dict(stats)
        t_start = time.perf_counter()
        with self.annotate("step"):
            eng.step()
        t_end = time.perf_counter()
        with self.annotate("observe"):
            tick = self._observe(s0, before, t_start, t_end)
        self.ticks.append(tick)
        return tick

    def _observe(self, s0, before, t_start, t_end) -> Tick:
        eng, stats = self.engine, self.engine.stats
        on_slot = {s.uid: s for s in eng.slots if s.active}
        chunks, keys = [], []
        for uid, r in list(self.live.items()):
            s = on_slot.get(uid)
            if s is not None:
                if r.admitted is None:
                    r.admitted = t_start
                cur0 = before.get(uid, 0)
                if s.cursor > cur0:
                    chunks.append((cur0, s.cursor - cur0,
                                   s.cursor >= r.prompt_len))
                n = len(s.generated)
            else:
                st = eng.status(uid)
                if st == self.eng.QUEUED:
                    continue
                if r.admitted is None:
                    r.admitted = t_start
                toks = eng.result(uid) if st == self.eng.FINISHED else None
                if uid in before and before[uid] < r.prompt_len:
                    cur0 = before[uid]
                    chunks.append((cur0, r.prompt_len - cur0, True))
                n = len(toks) if toks is not None else len(r.token_times)
                r.status, r.tokens, r.done = st, toks, t_end
                del self.live[uid]
                self.done.append(r)
            for j in range(len(r.token_times), n):
                r.token_times.append(t_end)
                if j >= 1:
                    keys.append(r.prompt_len + j)
        if self.closed:
            for _ in range(int(self.mix["clients"]) - len(self.live)):
                self._submit(self._take(), time.perf_counter())
        d = lambda k: stats[k] - s0[k]
        return Tick(start=t_start, end=t_end,
                    prefill_dispatches=d("prefill_dispatches"),
                    decode_dispatches=d("decode_dispatches"),
                    prefill_chunks=chunks, decode_steps=d("decode_steps"),
                    decode_steps_advanced=d("decode_steps_advanced"),
                    decode_keys=keys)

    def serve_until(self, deadline: float) -> None:
        while time.perf_counter() < deadline:
            if not self.closed:
                self._send_due()
            if self.engine.in_flight == 0:
                if self.closed or self.next >= len(self.pool):
                    return
                wait = min(deadline, self.origin + self.pool[self.next].due)
                with self.annotate("wait"):
                    time.sleep(max(0.0, wait - time.perf_counter()))
                continue
            self.tick()

    def serve_ticks_until(self, cond: Callable[[], bool], limit: int) -> None:
        for _ in range(limit):
            if cond():
                return
            self.tick()
        raise RuntimeError("the cell's start condition was not reached")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    breakdown: Optional[dict]
    compared: Dict[str, dict]
    diagnostics: dict
    run: Run


def warm_up(client: Client, mix: dict, vocab: int, seed: int) -> None:
    """Compile every program the cell's traffic reaches: a prompt one token
    longer than the admission chunk (a chunk without and a chunk with the
    end-of-prompt work) and two decode steps, on the cell's own engine."""
    eng = client.engine
    chunk = min(int(mix["max_len"]), 512)
    n = min(max(int(mix["prompt"]["max"]), chunk + 1), int(mix["max_len"]) - 2)
    prompt = T.rng_for(seed, 5).integers(0, vocab, size=n, dtype=np.int32)
    uid = eng.submit(prompt, max_new_tokens=2)
    while eng.in_flight:
        eng.step()
    eng.result(uid)


def sample_requests(done: List[Req], mix: dict, seed: int) -> List[Req]:
    """The finished requests the reference checks: the longest, then others
    drawn from the seed, until ``check_tokens`` served tokens or
    ``check_requests`` requests."""
    fin = [r for r in done if r.tokens]
    if not fin:
        return []
    fin.sort(key=lambda r: (-(r.prompt_len + len(r.tokens)), r.uid))
    pick = [fin[0]]
    rest = fin[1:]
    order = T.rng_for(seed, 4).permutation(len(rest))
    for i in order:
        if (len(pick) >= int(mix["check_requests"])
                or sum(len(r.tokens) for r in pick) >= int(mix["check_tokens"])):
            break
        pick.append(rest[i])
    return pick


def gap_numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    """The numbers compared with their limits, from the per-token gaps (the
    reference's best logit minus its logit of the served token): the
    widest, and the mean over every checked token."""
    if not gaps:
        return {}
    allg = np.concatenate(gaps)
    return {"logit_gap": float(allg.max()), "mean_logit_gap": float(allg.mean())}


def reference_gaps(cell: Cell, seed: int, picked: List[Req],
                   control: bool = False):
    from chipbench.reference import Sequence
    ref = cell.kind.Reference(cell.config, int(cell.mix["max_len"]), seed)
    seqs = [Sequence(np.asarray(r.prompt, np.int32),
                     np.asarray(r.tokens, np.int32)) for r in picked]
    return ref.gaps(seqs, control=control)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, program=None, mix_override: Optional[dict] = None,
             check: bool = True, control: bool = False) -> Outcome:
    import jax
    from chipbench import tracereduce as TR
    mix = dict(cell.mix, **(mix_override or {}))
    program = program or import_program()
    rc, enable_compile_cache, decoder, eng_mod = program
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = CompileMeter()
    dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None

    params = cell.kind.program_params(cell.config, seed)
    jax.block_until_ready(params)
    engine, cfg = build_engine(dataclasses.replace(cell, mix=mix), params, program)
    check_params_tree(params, cfg, decoder)
    vocab = int(cell.config["vocab_size"])
    reqs = T.generate(mix, seed, seconds, vocab)

    annotate = lambda name: jax.profiler.TraceAnnotation(f"chipbench.{name}")
    client = Client(engine, eng_mod, mix, reqs, annotate)
    warm_up(client, mix, vocab, seed)
    start = mix.get("start", "none")
    client.origin = time.perf_counter()
    if mix["loop"] == "closed":
        client.start_closed()
    if start == "slots_decoding":
        client.serve_ticks_until(
            lambda: all(s.decoding for s in engine.slots), MAX_WARM_TICKS)
    elif start == "preroll":
        client.serve_until(client.origin + float(mix["preroll_s"]))
    jax.block_until_ready(engine.cache)
    trace_dir = OUT / f"trace-{cell.name}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    n_ticks0 = len(client.ticks)
    m0 = meter.snapshot()
    late0 = len(client.lateness)
    stats0 = dict(engine.stats)
    t0 = time.perf_counter()
    with annotate("window"):
        client.serve_until(t0 + seconds)
        jax.block_until_ready(engine.cache)
    t1 = t0 + seconds
    m1 = meter.snapshot()
    counters = {k: v - stats0[k] for k, v in engine.stats.items()
                if isinstance(v, (int, float)) and k in stats0}
    mem = dev.memory_stats() or {}
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        events = TR.read(TR.newest_xplane(trace_dir))
        reduced = TR.reduce(events)
        trace_summary = TR.summary(events)
        del events
        shutil.rmtree(trace_dir, ignore_errors=True)

    window_ticks = client.ticks[n_ticks0:]   # as the counters: the window's loop
    run = Run(t0=t0, t1=t1, setup_s=t0 - t_process, loop=mix["loop"],
              requests=client.all, ticks=window_ticks,
              work=cell.kind.Work(cell.config, int(mix["max_len"])),
              peaks=peaks, trace=reduced, counters=counters)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- requests: attempted in the window, failed at any time ----------------
    sent_in = [r for r in client.all if t0 <= r.sent <= t1]
    bad = [r for r in client.done
           if r.status != eng_mod.FINISHED
           or r.tokens is None or len(r.tokens) != r.max_new
           or any(not (0 <= t < vocab) for t in r.tokens)]
    window_done = [r for r in client.done if r.done is not None and r.done <= t1]
    diag = {
        "cache_dir": cache_dir,
        "lateness_p50_ms": _ms(percentile(client.lateness[late0:], 50)),
        "lateness_p99_ms": _ms(percentile(client.lateness[late0:], 99)),
        "window_compiles": {k: m1[k] - m0[k] for k in m0},
        "setup_compiles": m0,
        "memory_stats": mem,
        "engine_stats": dict(engine.stats),
        "window_ticks": len(window_ticks),
        "window_dispatches": (counters["prefill_dispatches"]
                              + counters["decode_dispatches"]),
        "finished_in_window": len(window_done),
        "in_flight_at_close": engine.in_flight,
    }
    if reduced is not None:
        diag["trace"] = dict(trace_summary, covered_s=reduced.covered_s,
                             cut_s=reduced.cut_s)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    breakdown = None
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = {"device_ops": [[k, v] for k, v in reduced.device_ops],
                     "idle_gaps": [[k, v] for k, v in reduced.idle_gaps]}

    # -- correctness: the reference over a sample of finished requests --------
    compared: Dict[str, dict] = {}
    correct = False
    if check:
        picked = sample_requests(window_done, mix, seed)
        del engine, params, client.engine
        gc.collect()
        t_ref = time.perf_counter()
        served, ctl = (reference_gaps(cell, seed, picked, control)
                       if picked else ([], None))
        diag["reference_s"] = time.perf_counter() - t_ref
        diag["checked_requests"] = len(picked)
        diag["checked_tokens"] = int(sum(len(g) for g in served))
        diag["gaps"] = gap_numbers(served)
        if ctl is not None:
            diag["control_gaps"] = gap_numbers(ctl)
        # compared: the numbers that limits/<cell>.json sets a limit for;
        # a cell without limits, or a limited number not read, is not correct
        limits = (cell.limits or {}).get("limits", {})
        compared = {name: {"value": diag["gaps"][name], "limit": limit}
                    for name, limit in limits.items() if name in diag["gaps"]}
        correct = (bool(picked) and not bad and bool(limits)
                   and len(compared) == len(limits)
                   and all(c["value"] <= c["limit"]
                           for c in compared.values()))
    return Outcome(correct=correct, attempted=len(sent_in), failed=len(bad),
                   metrics=metrics, device=device, breakdown=breakdown,
                   compared=compared, diagnostics=diag, run=run)


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else x * 1e3
