"""Smoke-size cells for the benchmark's own tests: the harness's code
paths on the CPU at sizes a test run holds. Never a measurement."""

from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]

# stands in for the peaks table in CPU runs; no number computed from it
# is a device number
STAND_IN_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def config(name: str = "olmo_1b") -> dict:
    """The named configuration cut to two layers of width 64."""
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    m = c["model"]
    small = {"d_model": 64, "n_layers": 2, "n_heads": 4,
             "n_kv": 2 if m["n_kv"] < m["n_heads"] else 4, "d_ff": 128,
             "vocab": 512}
    m.update(small)
    c["overrides"] = dict(c["overrides"], **small, q_chunk=64,
                          vocab_pad_to=64)
    c["engine"] = {"max_batch": 4, "page_size": 16, "n_pages": 33,
                   "hot_pages": 8, "batch_past_pages": 8}
    # smoke-size readings (CPU, seeds 0-19, every lane and up to 16
    # finished requests checked): program 0.000-0.030, fp8 control
    # 0.113-0.275; the planted faults 3.9-7.1
    c["check"] = {"logit_gap_limit": 0.05}
    return c


def closed_mix() -> dict:
    return {"arrivals": "closed", "clients": "max_batch",
            "prompt": {"dist": "uniform", "lo": 24, "hi": 72},
            "output": {"dist": "uniform", "lo": 8, "hi": 24},
            "check_sample": 16}


def open_mix(rate: float = 8.0) -> dict:
    return {"arrivals": "open_poisson", "rate_per_s": rate,
            "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6,
                       "lo": 16, "hi": 96},
            "output": {"dist": "uniform", "lo": 4, "hi": 16},
            "warmup_requests": 2, "check_sample": 3}


# every metric the harness can compute, as BENCHMARK.json would list it
END_TO_END = {"ttft_p90_ms": "ms", "itl_p95_ms": "ms",
              "out_tok_s": "tokens/s", "setup_s": "s"}
PER_LAYER = {"decode_lanes_mean": "lanes", "decode_mfu": "%",
             "paged_decode_roofline": "%", "mfu": "%",
             "device_idle_share.batch": "%"}


def bench(cell: str, metrics: list[str]) -> dict:
    """A benchmark definition holding one cell, ``setup_s`` and the given
    metrics."""
    def specs(table, names):
        return [{"name": n, "unit": u, "workloads": [cell]}
                for n, u in table.items() if n in names]
    return {"end_to_end": specs(END_TO_END, set(metrics) | {"setup_s"}),
            "per_layer": specs(PER_LAYER, set(metrics))}


def cell(name: str) -> dict:
    return {"name": name, "config": "smoke", "traffic": "smoke", "chips": 1}
