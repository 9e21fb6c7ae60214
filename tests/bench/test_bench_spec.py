"""``BENCHMARK.json`` itself: every cell finds its configuration, traffic
mix and metric readers by name, and every name keeps to the allowed
characters."""

import json
import os
import re

import pytest

from bench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def first_bad_name(bench):
    """The first name or unit outside the allowed characters, or None."""
    names = ([c["name"] for c in bench["configs"]]
             + [k for c in bench["configs"] for k in c["reduced"]]
             + [w[k] for w in bench["workloads"]
                for k in ("name", "config", "traffic")]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    for n in names:
        if not NAME.match(n):
            return n
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]):
            return m["unit"]
    return None


def test_names_and_units_use_only_allowed_characters():
    assert first_bad_name(BENCH) is None


def test_first_bad_name_finds_a_space():
    bad = json.loads(json.dumps(BENCH))
    bad["per_layer"][0]["unit"] = "events per s"
    assert first_bad_name(bad) == "events per s"


def test_metric_files_are_named_from_their_metric():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert os.path.isfile(spec.metric_file(m["name"])), m["name"]


def test_command_and_paths_stay_inside_the_benchmark():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_file_by_name(name):
    cell = spec.resolve(BENCH, name)
    assert cell.mix["entry"] in cell.family.DRIVERS
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_every_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.resolve(BENCH, "no_such.cell")


@pytest.mark.parametrize("config, build", [
    ("flavor_lstm", "flavor_tagging"), ("quickdraw_lstm", "quickdraw")])
def test_configuration_files_hold_the_paper_sizes(config, build):
    """The sizes as run equal the program's own paper configurations."""
    import importlib

    want = importlib.import_module(f"repro.configs.{build}").lstm_config()
    file = spec.load_json(
        os.path.join(spec.ROOT, "bench", "configs", f"{config}.json"))
    got = spec.family(file["family"]).model_config(file)
    assert got.rnn == want.rnn
    assert (got.param_dtype, got.compute_dtype) == \
        (want.param_dtype, want.compute_dtype)
