"""``BENCHMARK.json`` keeps to its contract, and every name in it leads to
the file that implements it."""
import json
import os
import re

import pytest

from chipbench import spec

DOC = spec.read_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in DOC["workloads"]}
E2E = {m["name"]: m for m in DOC["end_to_end"]}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_budget():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(DOC)) <= 64 * 1024
    assert 1 <= len(DOC["paths"]) <= 16
    for p in DOC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    assert len(DOC["command"]) <= 32 and all(map(_text, DOC["command"]))
    rs = DOC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs_cells_and_limits_resolve():
    bench = spec.Benchmark()
    used = {w["config"] for w in DOC["workloads"]}
    for c in DOC["configs"]:
        assert NAME.match(c["name"]) and c["name"] in used
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _text(c["source"]) and _text(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in DOC["paths"]))
        cfg = spec.read_json(os.path.join(spec.ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg.get("reduced", {}))
        spec.model_module(cfg["model"])
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 2)
    pairs = set()
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _text(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        bench.traffic(w)
        assert {"max_logit_gap", "checked_tokens"} <= set(bench.limits(w))


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_keep_to_the_contract(kind):
    names = set()
    for m in DOC[kind]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m["workloads"]) <= set(CELLS) if "workloads" in m \
            else True
        if kind == "end_to_end":
            assert set(m) <= {"name", "unit", "better", "bound", "source",
                              "workloads"}
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert set(m) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert _text(m["layer"]) and m["moves"] in E2E
            assert callable(spec.metric_reader(m["name"]).read)
            if "roofline" in m["name"] or "mfu" in m["name"]:
                assert m["unit"] == "%"


def test_every_cell_reports_what_it_must():
    bench = spec.Benchmark()
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for w in DOC["workloads"]:
        e2e = {m["name"] for m in bench.metrics(w, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = bench.metrics(w, "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_kernel_rooflines_have_a_step_mfu_beside_them():
    for m in DOC["per_layer"]:
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            mfu = [x for x in DOC["per_layer"] if "mfu" in x["name"]
                   and x["moves"] == m["moves"]
                   and set(x.get("workloads", CELLS))
                   >= set(m.get("workloads", CELLS))]
            assert mfu, m["name"]
