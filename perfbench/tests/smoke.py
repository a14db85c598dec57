"""Smoke-size cells for the CPU tests: the real runners, references and
readers over tiny widths (the kernels run in interpret mode)."""

from __future__ import annotations

import copy
import json
import types

from harness import registry
from harness.common import BENCH_DIR

QWEN_SMOKE = {"hidden_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "intermediate_size": 256, "vocab_size": 1000,
              "vocab_pad_multiple": 256,
              "head": {"kind": "block_sparse", "block": 64, "density": 0.5,
                       "pattern_seed": 7},
              "serving": {"max_slots": 4, "page_size": 16, "max_seq": 64}}
MAMBA_SMOKE = {"d_model": 128, "n_layer": 2, "vocab_size": 1000,
               "vocab_pad_multiple": 256, "d_state": 16, "headdim": 32,
               "chunk_size": 32,
               "head": {"kind": "block_sparse", "block": 64, "density": 0.5,
                        "pattern_seed": 7},
               "serving": {"max_slots": 4, "page_size": 16, "max_seq": 64}}
CHAT_SMOKE = {"arrival": {"process": "poisson", "rate_per_s": 8.0},
              "prompt": {"median": 24, "sigma": 0.5, "min": 16, "max": 32,
                         "lengths": [16, 32]},
              "output": {"median": 6, "sigma": 0.5, "min": 4, "max": 8},
              "check": {"requests": 8, "batch": 4}}
HEAD_SMOKE = {"sequences": 2, "tokens_per_sequence": 64}
LIMITS = {"logit_gap": 0.1, "head_logits_rel_err": 0.01,
          "head_dA_rel_err": 0.01, "head_dh_rel_err": 0.01}


def cell(workload: str) -> registry.Cell:
    """The named cell of BENCHMARK.json, its configuration and traffic cut
    to smoke size (widths included — a CPU test, not a cell)."""
    c = registry.load_cell(workload)
    spec = copy.deepcopy(c.config)
    spec.update(QWEN_SMOKE if "hidden_size" in spec else MAMBA_SMOKE)
    spec["limits"] = dict(LIMITS)
    traffic = copy.deepcopy(c.traffic)
    traffic.update(CHAT_SMOKE if traffic["kind"] == "serve" else HEAD_SMOKE)
    c.config, c.traffic = spec, traffic
    return c


def args(seed=123456789012, seconds=2.0, trace=0, trace_dir=None):
    return types.SimpleNamespace(workload="smoke", seed=seed,
                                 seconds=seconds, trace=trace,
                                 trace_dir=trace_dir)


def benchmark():
    with open(BENCH_DIR.parent / "BENCHMARK.json") as f:
        return json.load(f)
