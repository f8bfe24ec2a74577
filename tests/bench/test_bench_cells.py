"""Every cell of BENCHMARK.json finds its files by name, and the traffic
generators give every seed the same work."""
import json
import re

import numpy as np
import pytest

from _benchcells import ROOT
from bench import harness as H

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
# what each driver reads from its configuration and its traffic
NEEDS = {
    "integration": ({"X", "Y", "Z", "mesh", "T", "dt", "y_tile",
                     "local_kernel", "exchange", "donate", "spacing",
                     "limit_max_rel_err"}, {"n_blocks", "warmup_calls"}),
    "ensemble_backlog": ({"slot", "T", "dt", "y_tile", "batch_size",
                          "n_steps", "spacing", "limit_max_rel_err"},
                         {"members", "warmup_calls", "spread",
                          "sample_per_call", "check_batch"}),
}


def test_benchmark_file_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units_use_the_allowed_characters():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in metrics]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        assert len({g["name"] for g in group}) == len(group)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for name in CELLS:
        cell = H.load_cell(name, BENCH)
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2, name
        assert cell.per_layer, name
        for m in cell.per_layer:
            assert m["moves"] in got, (name, m["name"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = H.load_cell(name, BENCH)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert conf["file"].startswith("bench/configs/")
    assert cell.config["name"] == conf["name"]
    assert cell.config["reduced"] == conf["reduced"]
    kind = cell.traffic["driver"]
    assert callable(H.driver(kind).run)
    need_cfg, need_traffic = NEEDS[kind]
    assert need_cfg <= set(cell.config), need_cfg - set(cell.config)
    assert need_traffic <= set(cell.traffic)
    for m in cell.per_layer:
        assert callable(H.metric_reader(m["name"]))
    assert w["chips"] in (1, 4)


def test_an_unknown_cell_or_file_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        H.load_cell("no-such-cell", BENCH)
    with pytest.raises(FileNotFoundError):
        H.driver("no_such_driver")
    with pytest.raises(FileNotFoundError):
        H.metric_reader("no_such_metric")


def test_a_metric_of_one_kind_of_cell_is_read_by_its_quantitys_reader():
    reader = H.metric_reader("device_idle_share")
    assert H.metric_reader("device_idle_share.serve") is not None
    assert (H.metric_reader("device_idle_share.serve").__code__
            is reader.__code__)
    with pytest.raises(FileNotFoundError):
        H.metric_reader("no_such_metric.serve")


# what a cut may never touch: the shapes of the source's deployment
WIDTHS = {"X", "Y", "Z", "slot", "spacing", "T", "y_tile", "operator"}


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_each_cut_is_listed_with_the_published_value_and_why(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert cfg["reduced"] == conf["reduced"]
    assert not set(cfg["reduced"]) & WIDTHS
    for key in cfg["reduced"]:
        assert key in cfg and key in cfg["published"], key
        assert cfg["why_reduced"][key], key
    assert not set(cfg.get("assumed", {})) & set(cfg["reduced"])


def test_fields_are_the_seeds_own():
    from bench import fields as F
    seed = 2 ** 33 + 5                      # more than 32 bits
    a = [np.asarray(f) for f in F.make_grid(seed, (8, 16, 8))]
    b = [np.asarray(f) for f in F.make_grid(seed, (8, 16, 8))]
    c = [np.asarray(f) for f in F.make_grid(seed + 2 ** 32, (8, 16, 8))]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    m = [np.asarray(f) for f in F.make_members(seed, 3, (8, 16, 8),
                                                 spread=0.05)]
    assert m[0].shape == (3, 8, 16, 8)
    assert not np.array_equal(m[0][0], m[0][1])
    with pytest.raises(ValueError):
        F.seed_key(-1)
