"""Tiny cells for the benchmark's CPU tests: the real configuration
files with their widths cut and their activations in float32 (so that a
sound program serves the float32 reference's own argmax), and traffic
scaled to a test run, on the program's xla backend."""

from __future__ import annotations

import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from harness import spec  # noqa: E402

TINY = {
    "qwen3-0.6b": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                   "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                   "vocab": 250, "vocab_pad_to": 128, "dtype": "float32"},
    "mamba2-2.7b": {"n_layers": 2, "d_model": 64, "vocab": 250,
                    "vocab_pad_to": 128, "dtype": "float32",
                    "ssm": {"d_state": 16, "head_dim": 16, "expand": 2,
                            "conv_width": 4, "chunk": 16, "n_groups": 1}},
}


def tiny_config(name: str) -> dict:
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg["program"].update(copy.deepcopy(TINY[name]))
    return cfg


def serve_traffic(prefill_chunk: int = 16) -> dict:
    arrivals = {"process": "poisson", "rate_per_s": 40.0, "warmup_s": 0.3}
    return {"kind": "serve", "param_dtype": "float32",
            "engine": {"max_slots": 4, "max_len": 96,
                       "prefill_chunk": prefill_chunk, "backend": "xla"},
            "arrivals": arrivals,
            "prompt_tokens": {"median": 20, "sigma": 0.5, "min": 4,
                              "max": 48},
            "output_tokens": {"median": 8, "sigma": 0.4, "min": 3,
                              "max": 16},
            "check": {"requests": 3}}


def train_traffic() -> dict:
    with open(ROOT / "bench" / "traffic" / "train.json") as f:
        tr = json.load(f)
    tr.update(batch=2, seq=16, backend="xla")
    return tr


def cell(config_name: str, traffic: dict, name: str = "tiny") -> spec.Cell:
    return spec.Cell(name=name, chips=1, config_name=config_name,
                     config=tiny_config(config_name), traffic_name=name,
                     traffic=traffic, end_to_end=[], per_layer=[])
