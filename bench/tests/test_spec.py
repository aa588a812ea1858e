"""BENCHMARK.json and the files it names: discovery by name, the
character rules, the peaks table, and refusing to run without a chip."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchlib import device, spec

ROOT = spec.ROOT


def _write(path: pathlib.Path, text: str) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        names = [m.name for m in cell.end_to_end]
        assert "setup_s" in names
        for m in cell.per_layer:
            assert m.moves in names, (cell.name, m.name)


def test_added_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix and per-layer metric are files
    plus entries; the harness needs no edit to find them."""
    bench = tmp_path / "bench"
    _write(bench / "configs" / "toy-1.json", json.dumps({"name": "toy-1"}))
    _write(bench / "traffic" / "mix-a.json",
           json.dumps({"kind": "serve", "rate": 3}))
    _write(bench / "metrics" / "toy.metric_ms.py",
           "def read(run):\n    return run.counters.get('x')\n")
    spec_path = tmp_path / "BENCHMARK.json"
    spec_path.write_text(json.dumps({
        "configs": [{"name": "toy-1", "file": "bench/configs/toy-1.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy-1",
                       "traffic": "mix-a", "chips": 1}],
        "end_to_end": [
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "source": "host_clock"}],
        "per_layer": [
            {"name": "toy.metric_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "toy", "moves": "ttft_p95_ms"},
            {"name": "other_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "toy",
             "moves": "train_tokens_per_s"}],
    }))
    cell = spec.load_cell("toy.cell", bench_dir=bench, spec_path=spec_path)
    assert cell.config == {"name": "toy-1"}
    assert cell.traffic["rate"] == 3
    assert [m.name for m in cell.per_layer] == ["toy.metric_ms"]

    class R:
        counters = {"x": 4.5}
    assert cell.readers["toy.metric_ms"](R) == 4.5


def test_missing_file_is_an_error(tmp_path):
    spec_path = tmp_path / "BENCHMARK.json"
    spec_path.write_text(json.dumps({
        "configs": [{"name": "a", "file": "bench/configs/a.json"}],
        "workloads": [{"name": "c", "config": "a", "traffic": "t",
                       "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    with pytest.raises(spec.SpecError):
        spec.load_cell("c", bench_dir=tmp_path / "bench",
                       spec_path=spec_path)


@pytest.mark.parametrize("name,ok", [
    ("olmo1b.serve.history-cf", True), ("ttft_p95_ms", True),
    ("_x", True), ("9lives", True), ("a" * 64, True), ("a" * 65, False),
    ("has space", False), ("a,b", False), ("a/b", False), ("-x", False),
    (".x", False), ("", False), ("µs", False)])
def test_name_rule(name, ok):
    if ok:
        assert spec.check_name(name) == name
    else:
        with pytest.raises(spec.SpecError):
            spec.check_name(name)


@pytest.mark.parametrize("unit,ok", [
    ("ms", True), ("tokens/s", True), ("%", True), ("count", True),
    ("x" * 16, True), ("x" * 17, False), ("tokens per s", False),
    ("µs", False), ("", False)])
def test_unit_rule(unit, ok):
    if ok:
        assert spec.check_unit(unit) == unit
    else:
        with pytest.raises(spec.SpecError):
            spec.check_unit(unit)


def test_peaks_of_a_known_device():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5"])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError):
        device.peaks(kind)


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "olmo1b.serve.history-cf", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_chip_no_result():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
