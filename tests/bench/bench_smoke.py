"""Smoke-size cells for the benchmark's CPU tests: the published layouts
of the two families at widths a test run can hold."""
from __future__ import annotations

import json
from pathlib import Path

DENSE = {
    "name": "dense-smoke", "reference": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "program": {"name": "dense-smoke", "family": "dense", "n_layers": 2,
                "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
                "vocab_size": 512, "rope_theta": 10000.0},
}
MAMBA2 = {
    "name": "mamba2-smoke", "reference": "mamba2",
    "d_model": 64, "n_layer": 2, "vocab_size": 512, "norm_epsilon": 1e-06,
    "tie_embeddings": True,
    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
                "headdim": 16, "ngroups": 1, "chunk_size": 256},
    "program": {"name": "mamba2-smoke", "family": "ssm", "n_layers": 2,
                "d_model": 64, "n_heads": 1, "n_kv_heads": 1,
                "attn_type": "none", "d_ff": 0, "vocab_size": 512,
                "ssm_state": 16, "ssm_expand": 2, "ssm_headdim": 16,
                "ssm_ngroups": 1, "conv_kernel": 4, "tie_embeddings": True},
}
MIX = {"loop": "closed", "batch": 3, "prompt_len": 8, "cache_len": 24,
       "output": {"kind": "lognormal", "median": 6, "sigma": 0.8,
                  "min": 2, "max": 15},
       "requests": 4000}
LIMITS = {"mean_logit_gap": 0.05, "served_logit_gap": None,
          "sample_requests": 4, "rows_per_call": 2, "min_checked_tokens": 10}


def write_spec(root: Path, confs=(DENSE, MAMBA2), mix=MIX, limits=LIMITS) -> dict:
    """A BENCHMARK.json-shaped spec with one cell per configuration, its
    files under ``root``."""
    bench = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    (bench / "traffic" / "smoke-mix.json").write_text(json.dumps(mix))
    src = Path(__file__).resolve().parents[2] / "bench"
    (bench / "peaks.json").write_text((src / "peaks.json").read_text())
    (bench / "metrics").symlink_to(src / "metrics")
    spec = json.loads((src.parent / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for conf in confs:
        path = bench / "configs" / f"{conf['name']}.json"
        path.write_text(json.dumps(conf))
        spec["configs"].append({"name": conf["name"], "source": "smoke",
                                "file": str(path), "reduced": [], "why": "test"})
        cell = f"{conf['name']}.smoke"
        spec["workloads"].append({"name": cell, "config": conf["name"],
                                  "traffic": "smoke-mix", "chips": 1,
                                  "why": "test"})
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    return spec


def wide(conf: dict, vocab: int = 8192, d: int = 128) -> dict:
    """``conf`` with a vocabulary and width at which the top logits lie
    close enough for a lower precision to change the first choice."""
    out = json.loads(json.dumps(conf))
    out["name"] = conf["name"] + "-wide"
    out["program"].update(name=out["name"], vocab_size=vocab, d_model=d)
    out["vocab_size"] = vocab
    out["hidden_size" if "hidden_size" in out else "d_model"] = d
    return out
