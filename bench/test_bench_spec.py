"""``BENCHMARK.json`` and the files it names: every cell's configuration,
traffic mix and limits, and every per-layer metric's reader, found by
name; names, units and keys in the allowed forms; inputs that repeat for
a seed."""

import importlib.util
import json
import os
import re

import pytest

from bench.traffic import generate

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    s = importlib.util.spec_from_file_location(f"m_{name.replace('.', '_')}",
                                               path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51


def test_names_units_and_keys(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}


def test_every_cell_finds_its_files(spec):
    configs = {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        for part in (("configs", w["config"]), ("traffic", w["traffic"]),
                     ("limits", w["name"])):
            assert os.path.exists(os.path.join(HERE, *part[:-1],
                                               f"{part[-1]}.json")), part
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)


def test_every_cell_finds_its_code_by_name(spec):
    """A cell's traffic kind names its driver (``bench/<kind>_cell.py``)
    and its configuration's ``reference`` names the architecture's module,
    which gives everything the harness needs of the architecture."""
    from bench import harness, reference

    for w in spec["workloads"]:
        mix = generate.load(w["traffic"])
        cell = harness.cell_module(harness.Run(
            spec, w, {}, mix, {}, 0, 1.0, False, 0.0))
        assert callable(cell.run_cell) and callable(cell.calibrate)
        with open(os.path.join(HERE, "configs", f"{w['config']}.json")) as f:
            arch = reference.of(json.load(f))
        for name in ("init_stacked", "train_reference", "forward_flops",
                     "param_count", "program_params"):
            assert callable(getattr(arch, name)), name
        assert callable(arch.Dims.from_config)
        assert set(arch.PROGRAM_KINDS.values()) <= set(arch.LAYER_KINDS
                                                      + arch.GLOBAL_KINDS)


def test_reduced_lists_every_changed_key(spec):
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(c["reduced"]) == sorted(cfg["reduced_from"])
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size")), key


def test_each_per_layer_metric_has_a_reader_that_moves_its_metric(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        mod = _reader(m["name"])
        assert mod.MOVES == m["moves"] and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
        assert mod.read({}) is None  # nothing to read: no number


def test_every_cell_reports_setup_another_metric_and_a_layer(spec):
    for w in spec["workloads"]:
        def has(m):
            return w["name"] in m.get("workloads", [w["name"]])
        e2e = [m["name"] for m in spec["end_to_end"] if has(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(has(m) for m in spec["per_layer"])


def test_train_batches_repeat_for_a_seed_and_differ_in_the_ring():
    mix = generate.load("train.dp4-int8")
    mix = dict(mix, seq_len=16, ring=4)
    a = generate.train_batches(2 ** 33 + 7, mix, 3, 1000)
    b = generate.train_batches(2 ** 33 + 7, mix, 3, 1000)
    c = generate.train_batches(2 ** 33 + 8, mix, 3, 1000)
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert not all((x[0] == y[0]).all() for x, y in zip(a, c))
    rows = {r.tobytes() for t, _ in a for r in t}
    assert len(rows) == 3 * 4  # every row of the ring differs
    t, labels = a[0]
    assert (t[:, 1:] == labels[:, :-1]).all()
