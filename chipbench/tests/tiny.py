"""Tiny configurations and traffic for the CPU tests: the published
configs' keys at toy widths, and the cells' mixes at toy lengths."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from chipbench.bench import Bench

HERE = Path(__file__).resolve().parents[1]


def dense() -> dict:
    c = json.loads((HERE / "configs" / "granite-3-8b.json").read_text())
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=250)
    c["assumed"] = dict(c["assumed"], head_dim=16)
    return c


def moe() -> dict:
    c = json.loads((HERE / "configs" / "granite-moe-1b-a400m.json").read_text())
    c.update(hidden_size=64, intermediate_size=32, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=250,
             num_local_experts=8, num_experts_per_tok=2)
    c["assumed"] = dict(c["assumed"], head_dim=16)
    return c


def train_mix() -> dict:
    t = json.loads((HERE / "traffic" / "train.seq4k.json").read_text())
    t.update(seq_len=32, global_batch=4, peak_lr=1e-3)
    return t


def serve_mix() -> dict:
    t = json.loads((HERE / "traffic" / "serve.b32.json").read_text())
    t.update(batch=4, prompt_len=4, decode_steps=12, cache_len=16, check_requests=3)
    return t


TRAIN_CELL = "granite-3-8b.train.seq4k"
SERVE_CELL = "granite-3-8b.serve.b32"
SERVE_E2E = ["serve_tokens_per_s", "itl_p95_ms"]
SERVE_LAYER = {"prefill_ms": "serve_tokens_per_s", "decode_step_ms": "itl_p95_ms",
               "serve_mfu": "serve_tokens_per_s",
               "decode_step_roofline": "serve_tokens_per_s",
               "device_idle_share.serve": "serve_tokens_per_s"}


class TinyBench(Bench):
    """The repository's BENCHMARK.json, with each cell's config and
    traffic swapped for toy sizes and limits given by the test. The
    serving cell and its metrics are added where the file has none, so
    that the serving driver is tested whichever cells the file holds."""

    def __init__(self, limits: dict):
        super().__init__()
        self._limits = limits
        spec = self.spec
        if SERVE_CELL not in {w["name"] for w in spec["workloads"]}:
            spec["workloads"].append({"name": SERVE_CELL, "config": "granite-3-8b",
                                      "traffic": "serve.b32", "chips": 1, "why": "test"})
        have = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
        spec["end_to_end"] += [{"name": n, "unit": "x", "better": "higher", "bound": 0.1,
                                "source": "host_clock", "workloads": [SERVE_CELL]}
                               for n in SERVE_E2E if n not in have]
        spec["per_layer"] += [{"name": n, "unit": "x", "better": "higher",
                               "source": "device_trace", "layer": "test", "moves": m,
                               "workloads": [SERVE_CELL]}
                              for n, m in SERVE_LAYER.items() if n not in have]

    def config(self, name: str) -> dict:
        return dense() if name == "granite-3-8b" else moe()

    def traffic(self, name: str) -> dict:
        return copy.deepcopy(train_mix() if name.startswith("train") else serve_mix())

    def limits(self, cell: str) -> dict:
        return self._limits
