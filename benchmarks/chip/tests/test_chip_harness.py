"""The harness driven end to end on the CPU at smoke size: results,
correctness, the control and planted faults. No number here is a device
measurement; the look for a chip is skipped (``require_tpu=False``)."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.chip import harness, traffic
from benchmarks.chip.tests import smoke

BIG_SEED = 2 ** 31 + 77
CLOSED_METRICS = ["itl_p95_ms", "out_tok_s", "decode_lanes_mean",
                  "decode_mfu", "paged_decode_roofline", "mfu",
                  "device_idle_share.batch"]
OPEN_METRICS = ["ttft_p90_ms", "itl_p95_ms", "decode_mfu"]


def run(kind, *, trace=False, tamper=None, control=False, seed=BIG_SEED,
        config="olmo_1b", seconds=2.0):
    name = f"smoke.{kind}"
    mets = CLOSED_METRICS if kind == "closed" else OPEN_METRICS
    mix = smoke.closed_mix() if kind == "closed" else smoke.open_mix()
    return harness.run_cell(
        smoke.bench(name, mets), smoke.cell(name), smoke.config(config),
        mix, seed=seed, seconds=seconds, trace=trace,
        t_start=time.perf_counter(), require_tpu=False,
        peak=smoke.STAND_IN_PEAK, tamper=tamper, control=control)


def test_closed_cell_traced_run_is_correct_and_reads_every_layer(capsys):
    r = run("closed", trace=True)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["failed"] == 0 and r["attempted"] > 0
    m = r["metrics"]
    # the CPU trace has no program executions to tell decode from prefill
    # by, so decode_mfu and paged_decode_roofline find nothing to read
    assert "decode_mfu" not in m and "paged_decode_roofline" not in m
    for name in ("decode_lanes_mean", "mfu", "device_idle_share.batch"):
        assert name in m, name
    assert 1 <= m["decode_lanes_mean"]["value"] <= 4
    assert 0 < m["device_idle_share.batch"]["value"] < 100
    assert "setup_s" not in m
    dev = r["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert r["breakdown"]["idle_gaps"]
    json.dumps(r)
    assert "compiles inside the window" in capsys.readouterr().out


def test_open_cell_reports_ttft_and_generator_lateness(capsys):
    r = run("open", config="chatglm3_6b")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"ttft_p90_ms", "setup_s"} | (
        {"itl_p95_ms"} & set(r["metrics"]))
    assert r["metrics"]["ttft_p90_ms"]["value"] > 0
    assert r["attempted"] == round(8.0 * 2.0)
    assert "generator lateness over" in capsys.readouterr().out


def test_ttft_is_taken_from_the_due_time():
    arr = harness.find("arrivals", "open_poisson").Arrivals(
        smoke.open_mix(), 1, max_batch=4)
    drv = harness.Driver(None, arr, 1, 512)
    drv.t0, drv.t1 = 100.0, 110.0
    for i, (due, sub, first) in enumerate([(100.0, 100.5, 101.0),
                                           (104.0, 104.0, 104.2)]):
        lv = harness.Live(traffic.Req(i, due - 100.0, 8, 4), i,
                          np.zeros(8, np.int32), due, sub, first=first,
                          outcome="done")
        drv.all.append(lv)
    e2e, attempted, failed = harness.end_to_end(drv, 10.0)
    assert attempted == 2 and failed == 0
    # p90 of (1.0 s, 0.2 s) from the due times, not 0.5 s from submission
    assert e2e["ttft_p90_ms"][0] == pytest.approx(
        np.percentile([1000.0, 200.0], 90))


class FakeRecord:
    def __init__(self):
        self.req = type("R", (), {"out": []})()
        self.done_t = None
        self.outcome = None


def test_itl_is_every_gap_between_tokens_stamped_in_the_window():
    recs = {0: FakeRecord(), 1: FakeRecord()}
    llm = type("L", (), {"records": recs})()
    drv = harness.Driver(llm, None, 1, 512)
    for rid in recs:
        drv.live[rid] = harness.Live(traffic.Req(rid, 0.0, 8, 9), rid,
                                     np.zeros(8, np.int32), 0.0, 0.0)
    # (time, tokens of request 0, tokens of request 1); window 10..20
    for now, n0, n1 in [(9.0, 1, 1), (10.5, 2, 1), (11.0, 3, 2),
                        (11.4, 4, 4), (20.5, 5, 5)]:
        if now >= 10.0:
            drv.t0 = 10.0
        if now > 20.0:
            drv.t1 = 20.0
        recs[0].req.out, recs[1].req.out = [7] * n0, [7] * n1
        drv.poll(now)
    # the gap across the window's start and the one after its end are
    # left out; request 1's two tokens handed over together are 0 apart
    assert drv.gaps == pytest.approx([0.5, 0.4, 0.4, 0.0])
    assert sum(lv.win_n for lv in drv.live.values()) == 6


def alter_token(llm):
    """A served token of one lane replaced where it is produced (every
    2nd step)."""
    be = llm.engine.backend
    step = be.decode_step
    n = [0]

    def wrapped(slots, tables, lengths):
        logits = step(slots, tables, lengths)
        n[0] += 1
        if n[0] % 2 == 0:
            logits = logits.at[slots[0], 7].set(1e4)
        return logits
    be.decode_step = wrapped


def drop_state(llm):
    """The decode step's state left unchanged: the token fed back is
    never advanced."""
    llm.engine.backend.commit_tokens = lambda next_tokens: None


def drop_half_batch(llm):
    """Half of the decode batch left out: its logits come back empty."""
    be = llm.engine.backend
    step = be.decode_step

    def wrapped(slots, tables, lengths):
        logits = step(slots, tables, lengths)
        for s in slots[len(slots) // 2:]:
            logits = logits.at[s].set(0.0)
        return logits
    be.decode_step = wrapped


@pytest.mark.parametrize("fault", [alter_token, drop_state,
                                   drop_half_batch])
def test_a_broken_served_path_reads_not_correct(fault):
    r = run("closed", tamper=fault)
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]


def test_fp8_control_fails_the_check_the_program_passes():
    r = run("closed", control=True, seconds=3.0)
    limit = r["checks"]["logit_gap"]["limit"]
    # the control's tokens through the same verdict: not correct
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > limit
    # the program's own tokens on the same sample: correct
    assert r["program"]["correct"] is True
    assert r["program"]["logit_gap"] <= limit


def test_command_refuses_to_time_on_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "olmo_1b.decode_batch", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "olmo_1b.decode_batch", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
