"""Arrival generators and lookup by name."""

import json
import shutil

import pytest

from benchmarks.chip import harness, traffic
from benchmarks.chip.tests import smoke


def open_gen(seed, mix=None):
    arr = harness.find("arrivals", "open_poisson").Arrivals(
        mix or smoke.open_mix(rate=4.0), seed, max_batch=4)
    arr.start(10.0)
    return arr


def schedule(arr, until):
    return [(r.idx, round(r.due_t, 9), r.prompt_len, r.max_tokens)
            for r in arr.due(until)]


def test_same_seed_same_open_schedule():
    big = 2 ** 31 + 12345
    assert schedule(open_gen(big), 30.0) == schedule(open_gen(big), 30.0)
    assert schedule(open_gen(big), 30.0) != schedule(open_gen(big + 1),
                                                     30.0)


def test_open_window_holds_the_same_work_for_every_seed():
    seen = []
    for seed in (1, 2, 3):
        arr = open_gen(seed)
        reqs = arr.due(9.999)
        assert len(reqs) == 40                       # 4/s x 10 s
        assert reqs[0].due_t == 0.0
        assert arr.next_due() == pytest.approx(10.0)
        seen.append((sorted(r.prompt_len for r in reqs),
                     sorted(r.max_tokens for r in reqs)))
    assert seen[0] == seen[1] == seen[2]


def test_open_generator_keeps_arriving_after_the_window():
    arr = open_gen(5)
    first = arr.due(10.0)
    later = arr.due(20.0)
    assert later and min(r.due_t for r in later) > 10.0
    assert len({r.idx for r in first + later}) == len(first + later)


def closed_gen(seed):
    return harness.find("arrivals", "closed").Arrivals(
        smoke.closed_mix(), seed, max_batch=4)


def test_closed_clients_start_mid_answer_and_replace_on_completion():
    arr = closed_gen(9)
    warm = arr.warmup()
    mix = smoke.closed_mix()
    assert len(warm) == 4 and sorted(r.client for r in warm) == [0, 1, 2, 3]
    for r in warm:          # prompt + age, the rest of the answer
        total = r.prompt_len + r.max_tokens
        assert mix["prompt"]["lo"] < total <= traffic.max_total(mix)
        assert r.max_tokens >= 1
    nxt = arr.on_done(warm[2], 5.0)
    assert [r.client for r in nxt] == [warm[2].client]
    assert mix["prompt"]["lo"] <= nxt[0].prompt_len <= mix["prompt"]["hi"]
    a, b = closed_gen(9), closed_gen(9)
    assert [(r.prompt_len, r.max_tokens) for r in a.warmup()] == \
        [(r.prompt_len, r.max_tokens) for r in b.warmup()]


def test_closed_loop_holds_the_same_work_for_every_seed():
    def lengths(seed):
        arr = closed_gen(seed)
        warm = arr.warmup()
        later = [arr.on_done(warm[0], 1.0)[0] for _ in range(40)]
        return ([(r.prompt_len, r.max_tokens) for r in warm],
                [(r.prompt_len, r.max_tokens) for r in later])
    (w1, l1), (w2, l2) = lengths(3), lengths(2 ** 31 + 5)
    assert w1 == w2 and l1 == l2    # the lanes take them in one order too


def test_stratified_lengths_cover_the_distribution():
    import numpy as np
    spec = {"dist": "lognormal", "median": 2048, "sigma": 0.7, "lo": 512,
            "hi": 7936}
    xs = traffic.stratified(spec, 1000, np.random.default_rng(0))
    assert min(xs) >= 512 and max(xs) <= 7936
    assert sorted(xs)[500] == pytest.approx(2048, rel=0.01)
    u = traffic.stratified({"dist": "uniform", "lo": 3, "hi": 6}, 8,
                           np.random.default_rng(0))
    assert sorted(u) == [3, 3, 4, 4, 5, 5, 6, 6]


def test_pieces_are_found_by_name_and_new_files_need_no_edit(tmp_path):
    bench = harness.BENCH
    for kind in ("arrivals", "metrics", "models", "traffic"):
        shutil.copytree(bench / kind, tmp_path / kind)
    (tmp_path / "metrics" / "new_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "traffic" / "new_mix.json").write_text(
        json.dumps(dict(smoke.open_mix(), rate_per_s=1.5)))
    (tmp_path / "arrivals" / "burst.py").write_text(
        "class Arrivals:\n    closed = False\n")
    assert harness.find("metrics", "new_metric.x", tmp_path).read(None) \
        == 42.0
    assert harness.find("traffic", "new_mix", tmp_path)["rate_per_s"] == 1.5
    assert harness.find("arrivals", "burst", tmp_path).Arrivals.closed \
        is False
    for name in ("decode_mfu", "device_idle_share.batch"):
        assert callable(harness.find("metrics", name).read)
    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for cell in b["workloads"]:
        _, c, config, mix = harness.load_cell(cell["name"])
        assert harness.find("arrivals", mix["arrivals"]).Arrivals
        assert harness.find("models", config["reference"]).logits_at
        assert traffic.max_total(mix) <= config["model"]["max_positions"]
