"""Serving launcher: load (or init) a model, run batched requests
through the slot engine, optionally with A^3 approximation.

  python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --requests 8 --prompt-len 64 --max-new 32 --a3 conservative
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import time

import jax
import numpy as np

from repro.config import A3Config, ServeConfig, get_arch, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.models import decoder
from repro.serve.chaos import ChaosConfig, ChaosInjector
from repro.serve.engine import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="admission-prefill chunk size in tokens (every "
                         "arch, incl. recurrent/hybrid stacks — the "
                         "mixer-state interface carries mid-prompt "
                         "state); 0 = default chunk of "
                         "min(max_len, 512)")
    ap.add_argument("--prefill-chunk-min", type=int, default=0,
                    help="adaptive admission chunking floor: ticks with "
                         ">= 1 decoding slot shrink the effective chunk "
                         "to this many tokens (cold queues drain at the "
                         "full chunk); 0 = fixed chunk")
    ap.add_argument("--page-size", type=int, default=64,
                    help="prefix-cache page granularity in tokens (trie "
                         "edge length)")
    ap.add_argument("--cache-pages", type=int, default=0,
                    help="paged prefix-cache budget (pages of "
                         "--page-size tokens; shared prompt prefixes "
                         "admit via one gather dispatch instead of "
                         "re-prefilling); 0 = disabled")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8"],
                    help="prefix-cache pool precision: int8 stores KV "
                         "pages (and A^3 sorted-key snapshots) with "
                         "per-page fp32 scales — ~2x cache residency at "
                         "equal HBM — dequantized inside the warm "
                         "gather; none = pool in serving dtype")
    ap.add_argument("--l2-bytes", type=int, default=0,
                    help="host-RAM L2 page-store budget in bytes: "
                         "prefix-cache evictions demote pages (KV + "
                         "int8 scales + mixer snapshots + A^3 sorted "
                         "keys) to checksummed host blobs instead of "
                         "freeing them, and later lookups promote "
                         "verified blobs back to the device pool; "
                         "0 = disabled (evictions free)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="write a crash-consistent engine checkpoint "
                         "(slots, queue, device cache, prefix trie + "
                         "L2 tier) to this directory after the run; "
                         "empty = no checkpoint")
    ap.add_argument("--restore", action="store_true",
                    help="restore the engine from --checkpoint-dir "
                         "before serving (continues any in-flight "
                         "requests token-for-token); the directory "
                         "must hold a checkpoint")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="decode steps per jitted dispatch (lax.scan with "
                         "in-graph sampling + A^3 re-sort; the host syncs "
                         "once per block)")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="decode-block harvests allowed to stay in "
                         "flight behind the tick loop: tick N's ring is "
                         "read back only after tick N+depth's dispatches "
                         "issue (the next block's tokens ride the "
                         "device-resident carry); 0 = synchronous "
                         "harvest (bit-identical historical behavior)")
    ap.add_argument("--stats-json", default="",
                    help="write a versioned engine-stats snapshot "
                         "(schema tag + config echo + counters + "
                         "metrics-registry dump when telemetry is on) "
                         "as JSON to this path after the run drains; "
                         "empty = no dump")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the serving telemetry plane: metrics "
                         "registry (TTFT/TPOT/queue-sojourn "
                         "histograms), per-request span tracing, and "
                         "in-graph A^3 quality probes (candidate "
                         "count + captured-score-mass ratio, sampled "
                         "per --telemetry-every). Adds zero host "
                         "syncs; token streams are bit-identical")
    ap.add_argument("--telemetry-every", type=int, default=8,
                    help="sample the A^3 quality probe on every N-th "
                         "decode-block dispatch")
    ap.add_argument("--metrics-json", default="",
                    help="write the metrics-registry snapshot "
                         "(counters/gauges/histograms + the legacy "
                         "stats view) as JSON to this path after the "
                         "run; implies --telemetry")
    ap.add_argument("--trace-out", default="",
                    help="write the request-lifecycle event log as "
                         "Chrome-trace JSON (chrome://tracing / "
                         "Perfetto) to this path after the run; "
                         "implies --telemetry")
    ap.add_argument("--retain-results", type=int, default=0,
                    help="bound terminal per-request bookkeeping to "
                         "this many entries (FIFO eviction; results "
                         "pop on first read); 0 = unbounded")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route decode attention through the fused "
                         "single-pass Pallas kernel (TPU)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="in-graph sampling temperature; 0 = greedy argmax")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission: maximum queued requests "
                         "(overload beyond it is load-shed per "
                         "--shed-policy); 0 = unbounded")
    ap.add_argument("--shed-policy", default="reject-new",
                    choices=["reject-new", "evict-oldest-queued"],
                    help="which request a full queue sheds (shed "
                         "requests terminate REJECTED, submit never "
                         "raises for overload)")
    ap.add_argument("--deadline-ticks", type=int, default=0,
                    help="per-request deadline in engine ticks "
                         "(requests not finished in time terminate "
                         "EXPIRED); 0 = no deadline")
    ap.add_argument("--chaos-rate", type=float, default=0.0,
                    help="chaos injection: per-site per-tick fault "
                         "probability (corrupt a decoding lane, fail a "
                         "page gather, abort a tick mid-phase); 0 = "
                         "injection off")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the deterministic chaos schedule "
                         "(a run is exactly reproducible from "
                         "(seed, rate))")
    ap.add_argument("--a3", default="off",
                    choices=["off", "conservative", "aggressive"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    a3 = {"off": A3Config(), "conservative": A3Config.conservative(),
          "aggressive": A3Config.aggressive()}[args.a3]
    telemetry = bool(args.telemetry or args.metrics_json or args.trace_out)
    serve = ServeConfig(slots=args.slots, max_len=args.max_len,
                        prefill_chunk=args.prefill_chunk or None,
                        prefill_chunk_min=args.prefill_chunk_min or None,
                        decode_block=args.decode_block,
                        use_kernel=args.use_kernel,
                        temperature=args.temperature,
                        sample_seed=args.seed,
                        page_size=args.page_size,
                        cache_pages=args.cache_pages,
                        max_queue=args.max_queue,
                        shed_policy=args.shed_policy,
                        deadline_ticks=args.deadline_ticks or None,
                        kv_quant=args.kv_quant,
                        l2_bytes=args.l2_bytes,
                        pipeline_depth=args.pipeline_depth,
                        telemetry=telemetry,
                        telemetry_every=args.telemetry_every,
                        retain_results=args.retain_results)

    chaos = None
    if args.chaos_rate > 0.0:
        chaos = ChaosInjector(ChaosConfig(seed=args.chaos_seed,
                                          rate=args.chaos_rate))

    params = decoder.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.restore:
        if not args.checkpoint_dir:
            ap.error("--restore requires --checkpoint-dir")
        engine = ServeEngine.restore(args.checkpoint_dir, params, cfg,
                                     a3=a3, chaos=chaos)
        print(f"restored engine from {args.checkpoint_dir} "
              f"(in_flight={engine.in_flight})")
    else:
        engine = ServeEngine.from_config(params, cfg, serve, a3=a3,
                                         chaos=chaos)

    rng = np.random.default_rng(args.seed)
    uids = [engine.submit(
        rng.integers(0, cfg.vocab_size, size=args.prompt_len),
        max_new_tokens=args.max_new) for _ in range(args.requests)]

    t0 = time.time()
    engine.run_to_completion()
    dt = time.time() - t0
    done = sum(1 for u in uids if engine.result(u) is not None)
    total_new = sum(len(engine.result(u) or []) for u in uids)
    by_status = collections.Counter(engine.status(u) for u in uids)
    print(f"arch={cfg.name} a3={args.a3} requests={done}/{len(uids)} "
          f"new_tokens={total_new} ({total_new / dt:.1f} tok/s, "
          f"{dt:.1f}s) statuses={dict(by_status)} stats={engine.stats}")
    if chaos is not None:
        print(f"chaos: seed={args.chaos_seed} rate={args.chaos_rate} "
              f"events={chaos.events} victims={sorted(chaos.injected_uids)}")
    if args.stats_json:
        snapshot = {
            # versioned schema so bench/reanalyze tooling can diff
            # runs (the flat dict lives under "stats", unchanged)
            "schema": "a3-serve-stats/v2",
            "config": {"arch": cfg.name, "a3": args.a3,
                       "smoke": bool(args.smoke),
                       "requests": args.requests,
                       "prompt_len": args.prompt_len,
                       "max_new": args.max_new,
                       "seed": args.seed,
                       "serve": dataclasses.asdict(serve)},
            "stats": dict(engine.stats),
        }
        if engine.tm is not None:
            snapshot["metrics"] = engine.tm.metrics_snapshot()
        with open(args.stats_json, "w") as f:
            json.dump(snapshot, f, indent=2, sort_keys=True)
        print(f"wrote engine stats to {args.stats_json}")
    if args.metrics_json and engine.tm is not None:
        engine.tm.write_metrics(args.metrics_json)
        print(f"wrote metrics snapshot to {args.metrics_json}")
    if args.trace_out and engine.tm is not None:
        engine.tm.write_trace(args.trace_out)
        print(f"wrote chrome trace to {args.trace_out}")
    if args.checkpoint_dir:
        engine.checkpoint(args.checkpoint_dir)
        print(f"checkpointed engine to {args.checkpoint_dir}")


if __name__ == "__main__":
    main()
