"""Every cell's files load by name, a cell and a model family added as
files alone are found, and BENCHMARK.json keeps to the shape the
benchmark's contract gives."""
import hashlib
import json
import re
import shutil

import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import tiny_cell

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOY_FAMILY = '''from benchmarks.chip.families import dense
from benchmarks.chip.families.dense import *  # noqa: F401,F403


def layout(a):
    spec = dense.layout(a)
    shape, std = spec["final_norm"]
    spec["final_norm"] = (shape, std, "float32")
    return spec


def entry_axes(path):
    return 2 if path == "layers/gate" else dense.entry_axes(path)


#: the step's named scopes that this family's own metric reads
SCOPES = ("grad", "update")
'''
#: a reader of the family's own scopes: device ms a step under any of them
TOY_METRIC = '''def read(red, run):
    p = run["roles"].get("inner")
    mine = set(run["family"].SCOPES)
    ns = sum(v for (name, pid, scope), v in red.scopes.items()
             if (name, pid) == (p.name, p.program_id) and scope in mine)
    return ns / p.count / 1e6 if ns else None
'''


def _digests(root):
    return {str(f.relative_to(root)): hashlib.sha256(f.read_bytes()).hexdigest()
            for f in root.rglob("*")
            if f.is_file() and "__pycache__" not in f.parts}


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_by_name(name):
    cell = harness.load_cell(name)
    cfg = cell.family.program_config(cell.config)
    assert cfg.num_layers == cell.config["num_hidden_layers"]
    # the batch statistics by their gap, or by their ratio's where the
    # trajectory before them moves the gap (PERF.md, the check)
    assert set(cell.limits) - {"stats", "stats_ratio"} == {
        "loss1", "loss", "grad", "change", "outer"}
    assert len(set(cell.limits) & {"stats", "stats_ratio"}) == 1
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.metric_reader(m["name"]))


def test_cell_added_as_files_alone_is_found(tmp_path):
    """A new configuration, model family, mix, cell, its pins and
    metrics need new files and new entries in BENCHMARK.json, and no
    edit of any file there was.  The family ``toy`` is the dense block
    with a float32 final norm and ``layers/gate`` compared by (layer,
    row); its metric reads the trace's scopes that the family names."""
    import jax.numpy as jnp

    from benchmarks.chip import trace_reduce
    from benchmarks.chip.tests import test_bench_pins, test_bench_scopes
    from benchmarks.chip.weights import make_weights

    shutil.copytree(harness.ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    here = tmp_path / "benchmarks" / "chip"
    (here / "families" / "toy.py").write_text(TOY_FAMILY)
    config = json.loads((here / "configs" / "qwen3-0.6b.json").read_text())
    config.update(name="qwen3-0.6b-l4", num_hidden_layers=4, family="toy")
    (here / "configs" / "qwen3-0.6b-l4.json").write_text(json.dumps(config))
    traffic = json.loads((here / "traffic" / "switch-2x4.json").read_text())
    traffic["inner_steps"] = 4
    (here / "traffic" / "switch-2x4-h4.json").write_text(json.dumps(traffic))
    (here / "limits" / "qwen3-0.6b-l4.switch-2x4-h4.json").write_text(
        json.dumps({"loss1": 1e-3, "loss": 1e-3, "grad": 0.1, "change": 0.5,
                    "stats": 0.2, "outer": 0.5}))
    (here / "metrics" / "rounds_traced.py").write_text(
        "def read(red, run):\n    return float(red.rounds)\n")
    (here / "metrics" / "toy_scopes_ms.py").write_text(TOY_METRIC)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="qwen3-0.6b-l4",
                                 file="benchmarks/chip/configs/"
                                      "qwen3-0.6b-l4.json"))
    bench["workloads"].append({"name": "qwen3-0.6b-l4.switch-2x4-h4",
                               "config": "qwen3-0.6b-l4",
                               "traffic": "switch-2x4-h4", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "rounds_traced", "unit": "rounds", "better": "higher",
        "source": "device_trace", "layer": "whole round",
        "moves": "tokens_per_s", "workloads": ["qwen3-0.6b-l4.switch-2x4-h4"]})
    bench["per_layer"].append({
        "name": "toy_scopes_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "inner step",
        "moves": "tokens_per_s", "workloads": ["qwen3-0.6b-l4.switch-2x4-h4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    name = "qwen3-0.6b-l4.switch-2x4-h4"
    test_bench_pins.write(name, tmp_path)

    cell = harness.load_cell("qwen3-0.6b-l4.switch-2x4-h4", root=tmp_path)
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["inner_steps"] == 4
    assert cell.family.__file__ == str(here / "families" / "toy.py")
    assert cell.family.program_config(cell.config).num_layers == 4
    assert [m["name"] for m in cell.per_layer] == ["rounds_traced",
                                                   "toy_scopes_ms"]
    read = harness.metric_reader("rounds_traced", root=tmp_path)

    class Red:
        rounds = 3
    assert read(Red, {}) == 3.0
    red = trace_reduce.reduce_trace(test_bench_scopes.TRACE)
    roles = test_bench_scopes.harness_roles(red)
    p = roles["inner"]
    want = sum(red.scopes[(p.name, p.program_id, s)]
               for s in cell.family.SCOPES) / p.count / 1e6
    got = harness.metric_reader("toy_scopes_ms", root=tmp_path)(
        red, {"roles": roles, "family": cell.family})
    assert got == pytest.approx(want, rel=1e-12) and got > 0
    test_bench_pins.check_pinned(name, tmp_path)
    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "BENCHMARK.json", "benchmarks/chip/families/toy.py",
        "benchmarks/chip/configs/qwen3-0.6b-l4.json",
        "benchmarks/chip/traffic/switch-2x4-h4.json",
        f"benchmarks/chip/limits/{name}.json",
        f"benchmarks/chip/testdata/pins/{name}.json",
        "benchmarks/chip/metrics/rounds_traced.py",
        "benchmarks/chip/metrics/toy_scopes_ms.py"}

    small = tiny_cell("qwen3-0.6b-l4.switch-2x4-h4", root=tmp_path)
    fam = small.family
    a = fam.arch_of(small.config)
    w = make_weights(fam.layout(a), 7, jnp.bfloat16)
    assert w["final_norm"].dtype == jnp.float32
    assert w["embed"].dtype == w["layers"]["gate"].dtype == jnp.bfloat16
    norms = harness.Norms(fam).leaf(w)
    assert norms["layers/gate"].shape == w["layers"]["gate"].shape[:2]
    assert norms["layers/up"].shape == (a.num_layers,)
    assert norms["final_norm"].shape == (1,)


@pytest.mark.parametrize("family", [None, "absent"])
def test_a_config_without_its_family_module_is_refused(tmp_path, family):
    config = {k: v for k, v in harness.load_cell(CELLS[0]).config.items()
              if k != "family"}
    if family:
        config["family"] = family
    with pytest.raises(ValueError, match="nofamily.json"):
        harness.family(config, tmp_path / "configs" / "nofamily.json")


def test_a_config_that_leaves_the_registry_is_refused():
    cell = harness.load_cell(CELLS[0])
    config = dict(cell.config, intermediate_size=4096)
    with pytest.raises(ValueError, match="not the configuration"):
        cell.family.program_config(config)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        config = json.loads((harness.ROOT / c["file"]).read_text())
        assert config["reduced"] == c["reduced"]
        assert config["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] == 1 and len(w["why"]) <= 200
        cell = harness.load_cell(w["name"])
        assert len(cell.end_to_end) >= 2 and cell.per_layer
