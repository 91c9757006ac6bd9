"""Cells at a size a CPU test run holds, with their own limits.

The limits here were read the way the chip's were (``PERF.md``): the
widest logit gap of the program's served tokens and of the float8 control
over several seeds at this size, and the limit set between them.
"""
import json
import os

from benchmark import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARCH = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
            d_ff=256, vocab_size=512, rope_theta=10000.0, rms_norm_eps=1e-6,
            mlp_kind="swiglu", norm_kind="rmsnorm", tie_embeddings=False,
            param_dtype="bfloat16", compute_dtype="bfloat16")
# program's widest gap 0.021, the control's narrowest 0.070 (seeds 1-6)
SERVE_LIMIT = 0.045
GRAPH_LIMIT = 0.02
BIG_SEED = 2 ** 33 + 29


def config() -> dict:
    return {"model": "yi-9b", "arch": dict(ARCH),
            "reduced": [k for k in ARCH if k != "rms_norm_eps"],
            "limits": {"max_logit_gap": SERVE_LIMIT,
                       "max_rel_err": GRAPH_LIMIT}}


def _traffic(name: str) -> dict:
    with open(manifest.traffic_path(ROOT, name)) as f:
        return json.load(f)


def serve_traffic(loop: str = "closed") -> dict:
    if loop == "open":
        return dict(_traffic("chat"), rate_per_s=20.0, warmup_s=0.5,
                    check_requests=4,
                    engine={"max_slots": 4, "max_seq": 128},
                    prompt={"dist": "lognormal", "median": 12, "sigma": 0.8,
                            "min": 4, "max": 40},
                    output={"dist": "lognormal", "median": 6, "sigma": 0.8,
                            "min": 2, "max": 16})
    return dict(_traffic("reason"), clients=4, requests=64, warmup_s=0.5,
                check_requests=4, engine={"max_slots": 4, "max_seq": 256},
                prompt={"dist": "uniform", "min": 4, "max": 12},
                output={"dist": "lognormal", "median": 30, "sigma": 0.6,
                        "min": 8, "max": 100})


def graph_traffic() -> dict:
    return dict(_traffic("layer-graph"), chunks=[128, 256], sample_calls=3,
                tuning={"min_window": 0.0005, "best_of": 1,
                        "fit_epochs": 200})


def cell(kind: str = "serve", loop: str = "closed") -> manifest.Cell:
    bench = manifest.load(ROOT)
    name = {"serve": "yi-9b.chat", "graph": "yi-9b.layer-graph"}[kind]
    c = manifest.cell(bench, ROOT, name)
    c.config = config()
    c.traffic = graph_traffic() if kind == "graph" else serve_traffic(loop)
    return c
