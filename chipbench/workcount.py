"""Operations and bytes the served algorithm needs, from the configuration's
shapes alone (never from the compiled program), and the chip peaks.

Counts are lower bounds on the work of exact attention: a decode step
reads every weight but the embedding table once, plus the valid K/V rows
of each lane. A faster implementation therefore cannot push a share of
the roofline past 100%.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Work:
    cfg: dict
    ring: int

    @property
    def d(self) -> int:
        return self.cfg["hidden_size"]

    @property
    def hd(self) -> int:
        return self.cfg["head_dim"]

    @property
    def layers(self) -> int:
        return self.cfg["num_hidden_layers"]

    @property
    def hq(self) -> int:
        return self.cfg["num_attention_heads"]

    @property
    def hkv(self) -> int:
        return self.cfg["num_key_value_heads"]

    @property
    def vocab(self) -> int:
        return self.cfg["vocab_size"]

    def layer_params(self) -> int:
        d, hd = self.d, self.hd
        attn = d * self.hq * hd * 2 + d * self.hkv * hd * 2
        ffn = 3 * d * self.cfg["intermediate_size"]
        return attn + ffn + 2 * d

    def params(self) -> int:
        embed = self.vocab * self.d
        head = 0 if self.cfg["tie_word_embeddings"] else self.vocab * self.d
        return self.layers * self.layer_params() + embed + head + self.d

    def weight_bytes(self) -> int:
        return self.params() * BF16

    def step_weight_bytes(self) -> int:
        """Weights one decode step reads: all but the embedding table."""
        embed = 0 if self.cfg["tie_word_embeddings"] else self.vocab * self.d
        return (self.params() - embed) * BF16

    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.hkv * self.hd * BF16

    # -- decode ----------------------------------------------------------------
    def decode_lane_bytes(self, keys: int) -> int:
        """Cache bytes one lane's decode step needs over ``keys`` positions."""
        return keys * self.kv_bytes_per_token()

    def decode_lane_flops(self, keys: int) -> int:
        """Model operations of one decoded token over ``keys`` positions."""
        dense = 2 * (self.layers * self.layer_params()
                     + self.vocab * self.d)
        return dense + self.layers * 4 * self.hq * self.hd * keys

    def decode_step_least_s(self, steps: int, lane_keys, peaks: dict) -> float:
        """Least time of ``steps`` decode steps that decoded tokens with the
        given key counts: the larger of the FLOP and the byte bound."""
        flops = sum(self.decode_lane_flops(k) for k in lane_keys)
        bytes_ = (steps * self.step_weight_bytes()
                  + sum(self.decode_lane_bytes(k) for k in lane_keys))
        return max(flops / peaks["bf16_flops_per_s"],
                   bytes_ / peaks["hbm_bytes_per_s"])

    # -- prefill ---------------------------------------------------------------
    def prefill_flops(self, start: int, length: int, last: bool) -> int:
        """Model operations of prompt positions start .. start+length-1
        (causal exact attention); ``last`` adds the unembedding of the
        prompt's final position."""
        dense = 2 * self.layers * self.layer_params() * length
        # sum over positions p of the p+1 keys each attends
        keys = length * start + length * (length + 1) // 2
        attn = self.layers * 4 * self.hq * self.hd * keys
        head = 2 * self.vocab * self.d if last else 0
        return dense + attn + head
