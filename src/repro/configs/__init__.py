"""Architecture registry: `get_config(<id>)` resolves an id here."""
from __future__ import annotations

import importlib

ARCHS = [
    "h2o_danube_1_8b",
    "qwen3_1_7b",
    "nemotron_4_340b",
    "qwen2_72b",
    "zamba2_2_7b",
    "arctic_480b",
    "deepseek_v2_236b",
    "qwen2_vl_72b",
    "hubert_xlarge",
    "rwkv6_3b",
    # the paper's own workload (clustering service) — see paper_cluster.py
    "paper_cluster",
]


def canon(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def get_config(name: str):
    mod = importlib.import_module(f"repro.configs.{canon(name)}")
    return mod.CONFIG


def all_arch_names():
    return [a for a in ARCHS if a != "paper_cluster"]
