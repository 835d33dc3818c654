"""Trace reduction: busy union, idle attribution and names, on events
built by hand and on a profile recorded here on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import trace_reduce as tr

E = tr.Event


def trace(ops, spans=(), programs=()):
    return tr.Trace(list(ops), list(programs), list(spans), (0.0, 100.0))


def test_busy_is_the_union_of_overlapping_ops():
    t = trace([E("d0", "a", 10, 30), E("d0", "b", 20, 40),
               E("d0", "c", 35, 50), E("d0", "d", 70, 80)])
    assert t.busy_intervals("d0") == [(10, 50), (70, 80)]
    assert t.busy_s() == pytest.approx(50e-9)


def test_busy_is_clipped_to_the_window_and_averaged_over_devices():
    t = trace([E("d0", "a", -10, 20), E("d0", "b", 90, 130),
               E("d1", "a", 0, 100)])
    assert t.busy_s() == pytest.approx(0.5 * (30 + 100) * 1e-9)


def test_idle_gaps_go_to_the_innermost_open_span():
    spans = [E("host", "bench.window", 0, 100),
             E("host", "bench.tick", 0, 60),
             E("host", "bench.page_state", 5, 25),
             E("host", "bench.wait", 70, 100)]
    t = trace([E("d0", "op", 30, 50), E("d0", "op", 60, 65)], spans)
    gaps = dict(t.idle_gaps())
    assert gaps["bench.page_state"] == pytest.approx(20e-9)   # 5..25
    assert gaps["bench.tick"] == pytest.approx(20e-9)   # 0..5 25..30 50..60
    assert gaps["bench.window"] == pytest.approx(5e-9)        # 65..70
    assert gaps["bench.wait"] == pytest.approx(30e-9)         # 70..100
    assert sum(gaps.values()) == pytest.approx(1e-7 - t.busy_s())


def test_programs_and_ops_by_stable_name():
    ops = [E("d0", "fusion.1", 0, 10), E("d0", "paged_kernel.3", 10, 30),
           E("d0", "fusion.2", 40, 45)]
    progs = [E("d0", "jit__decode_fn(12)", 0, 30),
             E("d0", "jit__prefill_chunk_batch_fn(3)", 40, 45)]
    tr._assign_modules(ops, progs)
    tr._self_times(ops)
    t = trace(ops, programs=progs)
    assert t.program_s("jit__decode_fn") == pytest.approx(30e-9)
    assert t.program_s("jit__prefill") == pytest.approx(5e-9)
    assert t.op_s("paged_kernel") == pytest.approx(20e-9)
    assert ops[2].module.startswith("jit__prefill")
    assert t.program_s("jit", holding="paged_kernel") == \
        pytest.approx(30e-9)
    top = dict(t.top_ops())
    assert top["jit__decode_fn(12):paged_kernel.3"] == pytest.approx(20e-9)


def test_a_loop_op_counts_only_its_own_time():
    ops = [E("d0", "%while.4 = (s32[], bf16[2]) while(%t)", 0, 50),
           E("d0", "%fusion.1 = bf16[8]{0} fusion(%a)", 5, 20),
           E("d0", "%closed_call.2 = bf16[8]{0} custom-call(%b), "
                   'custom_call_target="tpu_custom_call"', 20, 45)]
    tr._self_times(ops)
    assert [e.self_ns for e in ops] == [10, 15, 25]
    top = dict(trace(ops).top_ops())
    assert top["%closed_call.2 custom-call bf16[8] tpu_custom_call"] == \
        pytest.approx(25e-9)
    assert top["%while.4"] == pytest.approx(10e-9)
    assert trace(ops).busy_s() == pytest.approx(50e-9)


def test_recorded_cpu_profile(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.latest_xplane(str(tmp_path)))
    assert t.window_s > 0
    assert {s.name for s in t.spans} >= {tr.WINDOW_SPAN, "bench.tick"}
    assert t.ops, "no device operations found in the CPU profile"
    assert 0 < t.busy_s() <= t.window_s
    assert t.program_s("lambda") > 0
    assert sum(s for _, s in t.idle_gaps()) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-6)
