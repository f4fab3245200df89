"""A tiny cell of the chip benchmark for CPU tests: the harness's whole
run, from weights to the reference comparison, at toy sizes."""
from __future__ import annotations

import dataclasses
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def tiny_config() -> dict:
    return {"name": "tiny", "arch": "chipbench-tiny",
           "a3": None, "reference": "dense_gqa",
           "hidden_size": 256, "intermediate_size": 512,
           "num_hidden_layers": 4, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 8192,
           "hidden_act": "silu", "rope_theta": 10000.0,
           "partial_rotary_factor": 1.0, "rope_scaling": None,
           "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
           "embedding_multiplier": math.sqrt(256)}


def tiny_mix(loop: str = "closed") -> dict:
    mix = {"loop": loop, "clients": 3, "rate_per_s": 4.0,
           "prompt": {"dist": "uniform", "min": 40, "max": 150},
           "output": {"dist": "uniform", "min": 8, "max": 100},
           "slots": 2, "max_len": 256, "start": "slots_decoding",
           "check_requests": 3, "check_tokens": 120}
    if loop == "open":
        mix.update(start="preroll", preroll_s=0.5)
    return mix


def register_tiny_arch():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.config import ModelConfig, register_arch

    @register_arch("chipbench-tiny")
    def _cfg():
        return ModelConfig(name="chipbench-tiny", family="dense",
                           num_layers=4, d_model=256, num_heads=4,
                           num_kv_heads=2, d_ff=512, vocab_size=8192,
                           head_dim=64, rope_theta=10000.0)


def tiny_cell(loop: str = "closed", limits=None,
              reference: str = "dense_gqa"):
    from chipbench import harness
    bench = harness.load_benchmark()
    config = dict(tiny_config(), reference=reference)
    return harness.Cell(name="tiny", chips=1, config=config,
                        kind=harness.load_kind(reference),
                        mix=tiny_mix(loop), end_to_end=bench["end_to_end"],
                        per_layer=bench["per_layer"],
                        limits={"limits": dict(limits or {})})
