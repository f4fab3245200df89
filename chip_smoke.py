"""Bring-up smoke run of the serving path on one TPU chip.

Serves phi4-mini-3.8b at its published widths (random weights from a
seed, nothing downloaded) through ``ServeEngine.from_config``, the entry
point ``repro.launch.serve`` uses, in three phases with one engine alive
at a time:

  (a) exact attention, default (jnp) decode path;
  (b) exact attention through the Pallas decode kernel (``use_kernel``);
  (c) A^3 conservative with telemetry on (captured-score-mass probe).

Each phase serves 8 seeded 512-token prompts, 32 new tokens each, on 4
slots with ``max_len`` 2048 (so slots are reused), and must finish every
request with in-vocabulary tokens and a balanced lifecycle ledger.
Before the phases, the decode kernel (fused and two-pass) is compared
with its jnp reference on seeded phi4-width inputs.

Timings printed here are smoke timings of a cold process (compilation
included), not benchmark metrics.

  python chip_smoke.py

Exits non-zero, printing no result line, unless JAX's default device is
a TPU. The last line of stdout is the JSON result.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import A3Config, A3Mode, ServeConfig, get_arch  # noqa: E402
from repro.kernels.decode_attention.kernel import (  # noqa: E402
    decode_attention)
from repro.kernels.decode_attention.ref import (  # noqa: E402
    decode_attention_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import decoder  # noqa: E402
from repro.serve.engine import (CTRL_COLS, FINISHED, ServeEngine,  # noqa: E402
                                make_decode_block_step)

ARCH = "phi4-mini-3.8b"
SEED = 0
REQUESTS, PROMPT_LEN, MAX_NEW = 8, 512, 32
SLOTS, MAX_LEN = 4, 2048
# decode kernel vs reference: bf16 inputs and outputs, f32 accumulation
# in both; the reference runs at highest matmul precision
KERNEL_ATOL = 2e-2


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


class CompileMeter:
    """Seconds JAX has spent tracing, lowering and compiling, and the
    persistent-cache hits, since this meter was created."""

    def __init__(self):
        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _memory() -> dict:
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def check_decode_kernel(*, b=4, hq=24, hkv=8, d=128, s=MAX_LEN,
                        threshold=None, seed=SEED, interpret=False) -> dict:
    """Max |kernel - reference| of the fused and two-pass decode kernels
    on seeded inputs with a ragged validity mask (default: phi4 widths at
    the smoke's ring length). Raises if either exceeds ``KERNEL_ATOL``."""
    if threshold is None:
        threshold = A3Config.conservative().threshold_nats
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, hq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, hkv, s, d), jnp.bfloat16)
    lens = jnp.asarray(np.linspace(s // 4, s, b).astype(np.int32))
    mask = jnp.broadcast_to(
        (jnp.arange(s)[None, :] < lens[:, None])[:, None, :], (b, hq, s))
    errs = {}
    for name, two_pass, thr in (("fused", False, None),
                                ("two_pass", True, threshold)):
        out = decode_attention(q, k, v, mask, threshold=thr,
                               exact_two_pass=two_pass, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            ref = decode_attention_ref(q, k, v, mask, threshold=thr)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        _check(np.isfinite(err) and err <= KERNEL_ATOL,
               f"decode kernel ({name}) max |err| {err} > {KERNEL_ATOL}")
        errs[name] = err
    return errs


def _decode_dispatch_hlo(engine: ServeEngine, cfg, a3: A3Config) -> str:
    """Compiled HLO of the engine's decode dispatch, rebuilt from the
    same step builder and argument shapes the engine dispatches."""
    step = make_decode_block_step(cfg, a3, steps=engine.decode_block,
                                  use_kernel=engine.use_kernel)
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    vec = jax.ShapeDtypeStruct((len(engine.slots),), jnp.int32)
    ctrl = jax.ShapeDtypeStruct((len(engine.slots), CTRL_COLS), jnp.int32)
    lowered = jax.jit(step, donate_argnums=(1,)).lower(
        jax.tree.map(shape, engine.params), jax.tree.map(shape, engine.cache),
        vec, vec, ctrl)
    return lowered.compile().as_text()


def serve_phase(params, cfg, *, a3: A3Config = A3Config(),
                use_kernel: bool = False, telemetry: bool = False,
                requests: int = REQUESTS, prompt_len: int = PROMPT_LEN,
                max_new: int = MAX_NEW, slots: int = SLOTS,
                max_len: int = MAX_LEN, seed: int = SEED) -> dict:
    """Serve ``requests`` seeded prompts through one engine and check the
    outcome; return the tokens, counters and smoke timings."""
    serve = ServeConfig(slots=slots, max_len=max_len, use_kernel=use_kernel,
                        telemetry=telemetry, sample_seed=seed)
    engine = ServeEngine.from_config(params, cfg, serve, a3=a3)
    rng = np.random.default_rng(seed)
    uids = [engine.submit(rng.integers(0, cfg.vocab_size, size=prompt_len),
                          max_new_tokens=max_new) for _ in range(requests)]
    t0 = time.perf_counter()
    engine.run_to_completion()
    out = {"wall_s": time.perf_counter() - t0, "tokens": [],
           "stats": dict(engine.stats)}

    for u in uids:
        st = engine.status(u)
        _check(st == FINISHED, f"request {u} ended {st!r}, not finished")
        toks = engine.result(u)
        _check(len(toks) == max_new,
               f"request {u}: {len(toks)} tokens, expected {max_new}")
        _check(decoder.POISON not in toks, f"request {u}: POISON emitted")
        _check(all(0 <= t < cfg.vocab_size for t in toks),
               f"request {u}: token outside [0, {cfg.vocab_size})")
        out["tokens"].append(toks)
    s = engine.stats
    terminal = (s["finished"] + s["rejected"] + s["cancelled"]
                + s["expired"] + s["failed"])
    _check(s["submitted"] == requests
           and s["submitted"] == terminal + engine.in_flight,
           f"conservation identity broken: {s}")

    if engine.tm is not None:
        h = engine.tm.h_a3_mass
        out["captured_mass"] = {"samples": h.total,
                                "mean": h.sum / max(h.total, 1)}
        if a3.mode != A3Mode.OFF:
            _check(h.total > 0, "A^3 probe recorded no samples")
    if use_kernel:
        out["tpu_custom_call"] = "tpu_custom_call" in _decode_dispatch_hlo(
            engine, cfg, a3)
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's default device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()

    errs = check_decode_kernel()
    print(f"decode kernel vs reference (B=4 Hq=24 Hkv=8 D=128 S={MAX_LEN}): "
          f"max |err| fused={errs['fused']:.3e} "
          f"two_pass={errs['two_pass']:.3e} (tolerance {KERNEL_ATOL})")

    cfg = get_arch(ARCH)
    t0 = time.perf_counter()
    params = jax.jit(lambda key: decoder.init_params(key, cfg))(
        jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{ARCH}: {n_params} params ({cfg.dtype}), init "
          f"{time.perf_counter() - t0:.1f}s, memory {_memory()}")

    phases = (("a_exact_jnp", {}),
              ("b_exact_kernel", {"use_kernel": True}),
              ("c_a3_conservative", {"a3": A3Config.conservative(),
                                     "telemetry": True}))
    results = {}
    for name, kw in phases:
        c0 = meter.seconds
        r = serve_phase(params, cfg, **kw)
        gc.collect()     # one engine alive at a time
        results[name] = r
        s = r["stats"]
        print(f"phase {name}: {s['finished']}/{REQUESTS} finished, "
              f"compile {meter.seconds - c0:.1f}s, smoke wall time (not a "
              f"metric) {r['wall_s']:.1f}s, decode_dispatches="
              f"{s['decode_dispatches']} prefill_dispatches="
              f"{s['prefill_dispatches']}, memory {_memory()}")
        if "captured_mass" in r:
            print(f"phase {name}: A^3 captured score mass "
                  f"{r['captured_mass']}")
        if "tpu_custom_call" in r:
            print(f"phase {name}: decode dispatch contains tpu_custom_call: "
                  f"{r['tpu_custom_call']}")
            _check(r["tpu_custom_call"],
                   "kernel phase's decode dispatch has no tpu_custom_call")

    a = np.asarray(results["a_exact_jnp"]["tokens"])
    b = np.asarray(results["b_exact_kernel"]["tokens"])
    print(f"token agreement (a) jnp vs (b) kernel: "
          f"{float((a == b).mean()):.4f} of {a.size} tokens (reported, not "
          f"asserted)")
    print(f"compile cache hits: {meter.cache_hits}, total compile "
          f"{meter.seconds:.1f}s")
    print(f"memory_stats: {dev.memory_stats()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
