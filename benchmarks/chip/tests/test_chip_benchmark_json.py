"""``BENCHMARK.json`` against the rules its reader holds it to, and the
seeded weights against the program's parameter layout."""

import dataclasses
import json
import re

import jax
import pytest

from benchmarks.chip import harness

ROOT = harness.ROOT
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in B["end_to_end"]}
CELLS = {w["name"]: w for w in B["workloads"]}


def cells_of(m):
    return m.get("workloads", list(CELLS))


def test_top_level_shape():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "benchmarks/chip/run.py"]
    assert B["paths"] == ["benchmarks/chip"]
    assert 1 <= B["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(B)) < 64 * 1024


def test_configs_cells_and_files():
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmarks/chip/")
        conf = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        assert set(conf["why_reduced"]) == set(conf["reduced"])
        assert conf["source"] == c["source"]
        for key in c["reduced"]:
            assert key in conf["published"]
            assert not re.search(r"(hidden|size|dim|rank|heads|channels)",
                                 key), key
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    assert len({(w["config"], w["traffic"]) for w in B["workloads"]}) == \
        len(B["workloads"])


def test_metrics_and_what_each_cell_reports():
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(cells_of(m)) <= set(CELLS)
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert E2E["setup_s"]["bound"] <= 0.25 and "workloads" not in \
        E2E["setup_s"]
    for m in B["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in E2E
        for cell in cells_of(m):
            assert cell in cells_of(E2E[m["moves"]]), (m["name"], cell)
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in CELLS:
        e2e = [m for m in B["end_to_end"] if cell in cells_of(m)]
        assert len(e2e) >= 2
        assert any(cell in cells_of(m) for m in B["per_layer"])
    # a kernel's roofline stands beside the whole step's share of peak
    assert any("mfu" in m["name"] for m in B["per_layer"]
               if m["moves"] == "itl_p95_ms")


@pytest.mark.parametrize("name", ["olmo_1b", "chatglm3_6b"])
def test_seeded_weights_have_the_programs_layout(name):
    from repro.configs import get_config
    from repro.models import lm

    conf = json.loads((harness.BENCH / "configs" / f"{name}.json")
                      .read_text())
    cfg = dataclasses.replace(get_config(conf["registry"]),
                              **conf["overrides"])
    model = harness.find("models", conf["reference"])
    want = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(
        lambda k: model.make_params(conf["model"], cfg.vocab_padded, k),
        jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
