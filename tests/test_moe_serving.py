"""MoE and QK-norm on the serving path: the dropless expert layer against
the capacity path, one chip's share of the experts, the routing counters,
and the spatial backend's refusal. CPU, smoke sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.models import lm, moe
from repro.serving import PagedEngineCfg, PagedServingEngine
from repro.serving.engine import Request
from repro.serving.scheduler import SchedulerCfg


def _smoke(**kw):
    return dataclasses.replace(get_smoke_config("olmoe_1b_7b"), **kw)


def _layer(dtype=jnp.bfloat16, n=48, capacity_factor=1.25):
    cfg = dataclasses.replace(_smoke().moe_cfg(), dtype=dtype,
                              capacity_factor=capacity_factor)
    params = moe.init(jax.random.PRNGKey(0), cfg, ep_hint=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n, cfg.d_model),
                          jnp.float32).astype(dtype)
    return cfg, params, x


def test_dropless_layer_is_batch_independent_where_capacity_drops():
    cfg, params, x = _layer(capacity_factor=0.5)
    whole, hist = moe.apply_dropless(params, cfg, x)
    alone = jnp.concatenate([moe.apply_dropless(params, cfg, x[:, i:i + 1])[0]
                             for i in range(x.shape[1])], axis=1)
    np.testing.assert_array_equal(np.asarray(whole, np.float32),
                                  np.asarray(alone, np.float32))
    assert int(hist.sum()) == x.shape[1] * cfg.top_k
    # the training layer drops assignments past its capacity, so a token's
    # output changes with the tokens beside it
    cap = moe.apply(params, cfg, x)[0]
    cap_alone = jnp.concatenate([moe.apply(params, cfg, x[:, i:i + 1])[0]
                                 for i in range(x.shape[1])], axis=1)
    assert not np.allclose(np.asarray(cap, np.float32),
                           np.asarray(cap_alone, np.float32), atol=1e-2)


def test_top_k_is_renormalised_only_when_asked():
    cfg, params, x = _layer(dtype=jnp.float32, n=8)
    assert cfg.renorm is False                  # OLMoE's norm_topk_prob
    pub = moe.apply_dropless(params, cfg, x)[0]
    ren = moe.apply_dropless(params, dataclasses.replace(cfg, renorm=True),
                             x)[0]
    # top-2 of 8 never holds all the mass: renormalising scales up
    ratio = np.linalg.norm(np.asarray(ren)) / np.linalg.norm(np.asarray(pub))
    assert ratio > 1.2


def test_four_held_shares_sum_to_the_whole_layer():
    cfg, params, x = _layer(dtype=jnp.float32)
    whole, hist = moe.apply_dropless(params, cfg, x)
    parts, hists = [], []
    for s in range(4):
        share = dict(params, **{k: params[k][2 * s:2 * s + 2]
                                for k in ("w1", "w2", "w3")})
        y, h = moe.apply_dropless(share, cfg, x, first=2 * s, held=2)
        parts.append(y)
        hists.append(h)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(hists), np.asarray(hist))


def test_a_held_share_serves_but_does_not_train():
    cfg = _smoke(experts_held=4, expert_first=4)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    b0 = params["blocks"]["b0"]
    assert b0["ffn"]["w1"].shape == (2, 4, 64, 32)
    assert b0["ffn"]["wg"].shape == (2, 64, 8)
    assert b0["core"]["q_norm"]["scale"].shape == (2, 64)
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "labels": jnp.zeros((1, 16), jnp.int32)}
    logits, cache = lm.prefill(params, cfg, {"tokens": batch["tokens"]})
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert cache["moe_hist"].shape == (2, 4)
    with pytest.raises(ValueError, match="does not train"):
        lm.loss_fn(params, cfg, batch)


def _engine(cfg, params, max_batch=4):
    pcfg = PagedEngineCfg(max_batch=max_batch, page_size=16, n_pages=64,
                          hot_pages=8, batch_past_pages=8, eos_id=-1)
    # a fixed prefill budget: every prompt below prefills in one wave
    return PagedServingEngine(cfg, params, pcfg,
                              SchedulerCfg(prefill_tokens=256))


def _served_logits(cfg, params, prompts, n_out=6):
    """Prefill and decode logits of the last request, served after the
    others (which take the earlier lanes and the front of the wave)."""
    eng = _engine(cfg, params)
    be = eng.backend
    target = len(prompts) - 1
    seen = {"prefill": [], "decode": []}
    wave, step = be.dispatch_wave, be.decode_step

    def slot_of_target():
        return next((s for s, r in eng.active.items() if r.rid == target),
                    None)

    def on_wave(*a, **k):
        out = wave(*a, **k)
        seen["prefill"].append(np.asarray(out[slot_of_target()],
                                          np.float32))
        return out

    def on_step(slots, tables, lengths):
        out = step(slots, tables, lengths)
        s = slot_of_target()
        if s in slots:
            seen["decode"].append(np.asarray(out[s], np.float32))
        return out

    be.dispatch_wave, be.decode_step = on_wave, on_step
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_tokens=n_out))
    while eng.active or eng.queue:
        eng.step()
    return seen


@pytest.mark.parametrize("path", ["dropless", "capacity"])
def test_served_logits_do_not_depend_on_batch_mates(path, monkeypatch):
    cfg = _smoke(star=None)
    if path == "capacity":
        # the training layer in the served path: the same comparison fails
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
        monkeypatch.setattr(
            moe, "apply_dropless",
            lambda p, c, x, **_: (moe.apply(p, c, x)[0],
                                  jnp.zeros((c.n_experts,), jnp.int32)))
    params = lm.init(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (45, 29, 52, 37)]
    alone = _served_logits(cfg, params, prompts[-1:])
    full = _served_logits(cfg, params, prompts)
    assert len(alone["prefill"]) == len(full["prefill"]) == 1
    assert len(alone["decode"]) == len(full["decode"]) == 5
    gap = max(float(np.abs(a - b).max()) for k in ("prefill", "decode")
              for a, b in zip(alone[k], full[k]))
    if path == "dropless":
        # the request's lane and its place in the wave differ, so sums
        # over masked keys may round apart; nothing else differs
        assert gap < 1e-2
    else:
        assert gap > 0.1


def test_routing_counters_and_their_sync():
    cfg = _smoke(star=None, experts_held=4, expert_first=2)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, 40).astype(np.int32)
               for _ in range(3)]

    eng = _engine(cfg, params)
    for i, p in enumerate(prompts):             # telemetry off: no pull
        eng.submit(Request(rid=i, prompt=p, max_tokens=4))
    before = len(obs.profiled_counts())
    while eng.active or eng.queue:
        eng.step()
    assert eng.backend.moe_hist is not None
    assert len(obs.profiled_counts()) == before

    tel = obs.Telemetry()
    eng = _engine(cfg, params)
    eng.attach_telemetry(tel)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_tokens=4))
    while eng.active or eng.queue:
        eng.step()
    a = tel.metrics.counter("engine_moe_assignments_total", "").series
    h = tel.metrics.counter("engine_moe_experts_hit_total", "").series
    got = {dict(k)["phase"]: v for k, v in a.items()}
    hits = {dict(k)["phase"]: v for k, v in h.items()}
    # every real token makes top_k choices; about half land on the 4 held
    # of 8 experts (padding and idle lanes are not counted)
    assert 0 < got["prefill"] <= 3 * 40 * 2 * cfg.n_layers
    assert 0 < got["decode"] <= 3 * 3 * 2 * cfg.n_layers
    assert 0 < hits["decode"] <= got["decode"]
    assert any(e["name"] == "moe.sync" for e in tel.tracer.events)


def test_spatial_backend_refuses_moe_and_qk_norm():
    from repro.spatial.engine import SpatialBackend, SpatialEngineCfg
    dense = get_smoke_config("olmo_1b")
    for cfg in (_smoke(star=None),
                dataclasses.replace(dense, star=None, qk_norm=True)):
        with pytest.raises(ValueError, match="MoE and QK-norm"):
            SpatialBackend(cfg, None, SpatialEngineCfg(), SchedulerCfg())
