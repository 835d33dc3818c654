"""The MoE configuration's pieces on the CPU: served logits against the
plain reference (``models/moe_decoder.py``) for prefill and for decode
through the paged cache, the share rule, the seeded weights' layout, the
harness at smoke size, and the readers of the routing counts. No number
here is a device measurement."""

import dataclasses
import json
import time
import types

import jax
import numpy as np
import pytest

from benchmarks.chip import counts, harness, moe_counts, trace_reduce
from benchmarks.chip.tests import smoke

BIG_SEED = 2 ** 31 + 1515
CONF = json.loads((harness.BENCH / "configs" / "olmoe_1b_7b.json")
                  .read_text())
REF = harness.find("models", "moe_decoder")


def small_model(**kw):
    """2 layers of width 64, 8 experts of width 32, top-2, 4 held."""
    m = dict(CONF["model"], d_model=64, n_layers=2, n_heads=4, n_kv=4,
             d_ff=32, vocab=512, n_experts=8, top_k=2, experts_held=4,
             expert_first=4)
    m.update(kw)
    return m


def program_cfg(m):
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("olmoe_1b_7b")
    assert (cfg.moe.n_experts, cfg.moe.top_k) == (m["n_experts"],
                                                  m["top_k"])
    return dataclasses.replace(cfg, star=None,
                               experts_held=m["experts_held"],
                               expert_first=m["expert_first"])


def test_served_logits_match_the_reference_in_prefill_and_decode():
    from repro.serving import LLM, PagedEngineCfg
    m = small_model()
    cfg = program_cfg(m)
    params = REF.make_params(m, cfg.vocab_padded, jax.random.PRNGKey(11))
    ecfg = PagedEngineCfg(max_batch=4, page_size=16, n_pages=64,
                          hot_pages=8, batch_past_pages=8, eos_id=-1)
    llm = LLM.from_config(cfg, backend="paged", params=params,
                          engine_cfg=ecfg)
    be = llm.engine.backend
    rows = {}                                   # (rid, position) -> logits
    wave, step = be.dispatch_wave, be.decode_step

    def rid_at(slot):
        return llm.engine.active[slot].rid

    def on_wave(flat, seg, pos, past_len, last_index, lanes):
        out = wave(flat, seg, pos, past_len, last_index, lanes)
        for lane in lanes:
            s = lane["slot"]
            rows[(rid_at(s), int(pos[last_index[s]]))] = out[s]
        return out

    def on_step(slots, tables, lengths):
        out = step(slots, tables, lengths)
        for s in slots:
            rows[(rid_at(s), int(lengths[s]))] = np.asarray(out[s])
        return out

    be.dispatch_wave, be.decode_step = on_wave, on_step
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, m["vocab"], n).astype(np.int32)
               for n in (40, 23, 57)]
    hs = [llm.submit(p, max_tokens=8) for p in prompts]
    llm.run_until_done()
    worst = {"prefill": 0.0, "decode": 0.0}
    for h, p in zip(hs, prompts):
        toks = np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
        pos = np.arange(len(p) - 1, len(toks))
        ref = REF.logits_at(m, params, toks, pos, bucket=64, reads=16)
        for i, q in enumerate(pos):
            got = np.asarray(rows[(h.rid, int(q))], np.float32)[:m["vocab"]]
            kind = "prefill" if i == 0 else "decode"
            worst[kind] = max(worst[kind], float(np.abs(got - ref[i]).max()))
            # the served token is the reference's best up to bf16 rounding
            assert ref[i].max() - ref[i][h.tokens[i]] < 0.05
    assert worst["prefill"] < 0.05 and worst["decode"] < 0.05, worst
    assert len(rows) == sum(len(h.tokens) for h in hs)


def test_four_shares_of_the_reference_layer_sum_to_the_uncut_layer():
    m = small_model()
    params = REF.make_params(dict(m, experts_held=8, expert_first=0), 512,
                             jax.random.PRNGKey(2))
    g = jax.tree.map(lambda a: a[0], params["blocks"]["b0"]["ffn"])
    u = jax.random.normal(jax.random.PRNGKey(3), (24, m["d_model"]))
    whole = REF.experts(dict(m, experts_held=8, expert_first=0), False, u, g)
    parts = [REF.experts(dict(m, experts_held=2, expert_first=2 * s), False,
                         u, dict(g, **{k: g[k][2 * s:2 * s + 2]
                                       for k in ("w1", "w2", "w3")}))
             for s in range(4)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # and the share is a real part: no one share gives the whole
    assert all(not np.allclose(np.asarray(p), np.asarray(whole), atol=1e-3)
               for p in parts)


@pytest.mark.parametrize("size", ["published", "smoke"])
def test_seeded_weights_have_the_programs_layout(size):
    from repro.configs import get_config
    from repro.models import lm
    if size == "published":
        m = CONF["model"]
        cfg = dataclasses.replace(get_config(CONF["registry"]),
                                  **CONF["overrides"])
    else:
        m = small_model()
        cfg = program_cfg(m)
    want = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(
        lambda k: REF.make_params(m, cfg.vocab_padded, k),
        jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


def test_published_share_sizes():
    m = CONF["model"]
    per_layer = 4 * 2048 * 2048 + 2048 * 64 + 16 * 3 * 2048 * 1024
    total = 16 * per_layer + 2 * 2048 * 50304
    shapes = REF.param_shapes(m, 50304)
    n = sum(int(np.prod(s)) for path, (s, _) in shapes.items()
            if "norm" not in path)
    assert n == total
    assert n == pytest.approx(2.09e9, rel=0.01)


def run_smoke(trace, tamper=None, seed=BIG_SEED):
    name = "smoke.moe"
    metrics = ["itl_p95_ms", "out_tok_s", "decode_lanes_mean"]
    b = smoke.bench(name, metrics)
    b["per_layer"] += [{"name": n, "unit": "%", "workloads": [name]}
                       for n in ("moe_mfu", "moe_gmm_roofline",
                                 "moe_decode_mfu")]
    return harness.run_cell(
        b, smoke.cell(name), smoke.config("olmoe_1b_7b"), smoke.closed_mix(),
        seed=seed, seconds=2.0, trace=trace, t_start=time.perf_counter(),
        require_tpu=False, peak=smoke.STAND_IN_PEAK, tamper=tamper)


def test_harness_runs_the_moe_cell_at_smoke_size():
    r = run_smoke(trace=True)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["moe_mfu"]["value"] > 0
    assert 1 <= m["decode_lanes_mean"]["value"] <= 4
    # the CPU lowers the grouped matmuls to plain dots: no ragged-dot
    # kernel in its trace, so the roofline finds nothing to read there
    assert "moe_gmm_roofline" not in m
    # nor program executions to find the decode program's time by
    assert "moe_decode_mfu" not in m
    r = run_smoke(trace=False)
    assert r["correct"], r["checks"]
    assert {"itl_p95_ms", "out_tok_s", "setup_s"} <= set(r["metrics"])


def drop_a_held_expert(llm):
    """One held expert's output zeroed in every layer of the served model."""
    be = llm.engine.backend
    p = jax.tree.map(lambda a: a, be.params)     # the check keeps its own
    ffn = p["blocks"]["b0"]["ffn"]
    ffn["w2"] = ffn["w2"].at[:, 3].set(0)
    be.params = p


def test_a_missing_expert_reads_not_correct():
    r = run_smoke(trace=False, tamper=drop_a_held_expert)
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]


def ctx_with(ops, window=(0.0, 1e9), programs=()):
    """A metric context over a hand-built trace: a 1-s window, two decode
    lanes of 100 and 200 positions and one 64-token prompt wave. An op may
    name the index of the program execution it runs in."""
    tr = trace_reduce.Trace(
        [trace_reduce.Event("tpu0", n, a, b, prog=i[0] if i else -1)
         for n, a, b, *i in ops],
        [trace_reduce.Event("tpu0", n, a, b) for n, a, b in programs], [],
        window)
    drv = types.SimpleNamespace(t0=0.0, t1=1.0)
    probe = types.SimpleNamespace(decodes=[(0.5, [100, 200])],
                                  waves=[(0.6, [(64, 0)])])
    ctx = harness.MetricContext(CONF["model"], counts.peaks("TPU v5 lite"),
                                tr, probe, drv, 1.0)
    return ctx


def test_readers_of_the_routing_counts(monkeypatch):
    from repro.obs import trace
    kept = [("engine.moe.decode", 0.5, {"assignments": 100,
                                        "experts_hit": 40}),
            ("engine.moe.prefill", 0.6, {"assignments": 300,
                                         "experts_hit": 60}),
            ("engine.moe.decode", 1.5, {"assignments": 7,
                                        "experts_hit": 7})]  # past window
    ops = [("%ragged-dot-none.2 = bf16[192,1024] custom-call(...)", 0, 2e6),
           ("%paged_decode.9 = bf16[24,16,1,128] custom-call(...)", 2e6,
            9e6)]
    ctx = ctx_with(ops)
    monkeypatch.setattr(trace, "profiled_counts", lambda: kept)
    assert moe_counts.window_counts(ctx) == {
        "dispatches": 2, "assignments": 400, "experts_hit": 100}
    assert moe_counts.window_counts(ctx, "decode") == {
        "dispatches": 1, "assignments": 100, "experts_hit": 40}
    f, b = moe_counts.gmm_cost(CONF["model"], 400, 100)
    assert f == 2 * 3 * 2048 * 1024 * 400
    assert b == 3 * 2048 * 1024 * 2 * 100 + 2 * 2048 * 2 * 400
    want = 100 * counts.roofline_s(f, b, ctx.peak) / 2e-3
    got = harness.find("metrics", "moe_gmm_roofline").read(ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    tok = 2 * (16 * (4 * 2048 * 2048 + 2048 * 64) + 2048 * 50304)
    per_key = 4 * 16 * 16 * 128
    flops = (2 * tok + per_key * 300 + 64 * tok
             + per_key * (64 * 65 // 2) + 2 * 3 * 2048 * 1024 * 400)
    mfu = harness.find("metrics", "moe_mfu").read(ctx)
    assert mfu == pytest.approx(100 * flops / 197e12)
    # no program executions in this trace: no decode program time
    assert harness.find("metrics", "moe_decode_mfu").read(ctx) is None


def test_decode_mfu_of_the_moe_model(monkeypatch):
    """Decode FLOPs, the experts' from the decode steps' counts alone,
    over the unnamed program that runs the paged kernel; the prefill wave,
    which runs expert kernels too, is not charged."""
    from repro.obs import trace
    kept = [("engine.moe.decode", 0.5, {"assignments": 100,
                                        "experts_hit": 40}),
            ("engine.moe.prefill", 0.6, {"assignments": 300,
                                         "experts_hit": 60})]
    progs = [("jit__unknown(1)", 0, 4e6),
             ("jit__prefill_chunk_batch_fn(2)", 5e6, 9e6)]
    ops = [("%paged_decode.9 = x custom-call(...) tpu_custom_call", 0, 2e6,
            0),
           ("%ragged-dot-none = x custom-call(...) tpu_custom_call", 2e6,
            3e6, 0),
           ("%ragged-dot-none = x custom-call(...) tpu_custom_call", 5e6,
            6e6, 1)]
    ctx = ctx_with(ops, programs=progs)
    monkeypatch.setattr(trace, "profiled_counts", lambda: kept)
    assert ctx.decode_program_s() == pytest.approx(4e-3)
    tok = 2 * (16 * (4 * 2048 * 2048 + 2048 * 64) + 2048 * 50304)
    per_key = 4 * 16 * 16 * 128
    flops = 2 * tok + per_key * 300 + 2 * 3 * 2048 * 1024 * 100
    assert moe_counts.decode_flops(CONF["model"], ctx.decodes(), 100) == \
        flops
    got = harness.find("metrics", "moe_decode_mfu").read(ctx)
    assert got == pytest.approx(100 * flops / (4e-3 * 197e12))


def test_readers_find_nothing_in_a_program_that_keeps_no_counts(
        monkeypatch):
    from repro.obs import trace
    monkeypatch.delattr(trace, "profiled_counts")
    ctx = ctx_with([("%ragged-dot-none = x", 0, 1e6)])
    assert moe_counts.window_counts(ctx) is None
    for name in ("moe_gmm_roofline", "moe_mfu", "moe_decode_mfu"):
        assert harness.find("metrics", name).read(ctx) is None
