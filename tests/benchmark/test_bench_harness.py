"""The harness finds every piece by name, so a configuration, a traffic
mix, a cell and a per-layer metric are added as files and entries only;
and a run refuses a CPU backend, printing no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness


def _copy_benchmark(dst):
    shutil.copytree(harness.BENCH_DIR, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), dst)


def test_new_pieces_are_found_by_name(tmp_path):
    root = str(tmp_path)
    _copy_benchmark(root)
    bench = os.path.join(root, "benchmark")
    cfg = harness.load_json(os.path.join(bench, "configs",
                                         "string-1024c.json"))
    cfg["ops_per_log"] = 5000
    with open(os.path.join(bench, "configs", "short-log.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "insert-heavy.json"), "w") as f:
        json.dump({"kind": "lagged_log", "caught_up_share": 0.9,
                   "op_weights": {"insert": 0.9, "remove": 0.05,
                                  "annotate": 0.05},
                   "max_insert_len": 8, "max_range_len": 16}, f)
    with open(os.path.join(bench, "metrics", "answer.count.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = harness.load_json(spec_path)
    spec["configs"].append({"name": "short-log", "source": "test",
                            "file": "benchmark/configs/short-log.json",
                            "reduced": ["ops_per_log"], "why": "test"})
    spec["workloads"].append({"name": "replay-short", "config": "short-log",
                              "traffic": "insert-heavy", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "replay_ops_per_s":
            m["workloads"].append("replay-short")
    spec["per_layer"].append({"name": "answer.count", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "replay_ops_per_s",
                              "workloads": ["replay-short"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {root!r})\n"
        "from benchmark import harness\n"
        "spec = harness.bench_spec()\n"
        "cell, cfg, trf = harness.find_cell(spec, 'replay-short')\n"
        "e2e, layer = harness.cell_metrics(spec, 'replay-short')\n"
        "drv = harness.driver_for(cfg)\n"
        "print(json.dumps({'ops': cfg['ops_per_log'],\n"
        "  'share': trf['caught_up_share'],\n"
        "  'e2e': [m['name'] for m in e2e],\n"
        "  'layer': [m['name'] for m in layer],\n"
        "  'driver': drv.__file__,\n"
        "  'answer': harness.metric_reader('answer.count')(None)}))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=root)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["ops"] == 5000 and got["share"] == 0.9
    assert got["e2e"] == ["replay_ops_per_s", "setup_s"]
    assert got["layer"] == ["answer.count"]
    assert got["driver"].startswith(os.path.join(bench, "drivers"))
    assert got["answer"] == 42.0
    # Nothing that was there before had to change.
    for sub in ("harness.py", "run.py", "traffic.py", "drivers/replay.py",
                "configs/string-1024c.json", "traffic/lagged-mix.json"):
        with open(os.path.join(harness.BENCH_DIR, sub), "rb") as a, \
                open(os.path.join(bench, sub), "rb") as b:
            assert a.read() == b.read(), sub


def test_cells_share_a_reader_by_stem():
    shared = os.path.join(harness.BENCH_DIR, "metrics", "device_idle_pct.py")
    for name in ("device_idle_pct.replay", "device_idle_pct.open"):
        assert harness.metric_reader(name).__code__.co_filename == shared
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no_such_metric.open")


def test_cached_arrays_follow_key_and_sources(tmp_path):
    import numpy as np

    src = tmp_path / "gen.py"
    src.write_text("one")
    made = []

    def make():
        made.append(1)
        return {"x": np.arange(5, dtype=np.int32) + len(made)}

    def get(seed):
        return harness.cached_arrays("t", {"seed": seed}, [str(src)], make)

    a, b = get(1), get(1)
    assert len(made) == 1 and np.array_equal(a["x"], b["x"])
    get(2)
    assert len(made) == 2
    src.write_text("two")  # a changed source never reads an old entry
    assert not np.array_equal(get(1)["x"], a["x"]) and len(made) == 3


def test_unknown_workload_is_refused():
    with pytest.raises(harness.BenchError):
        harness.find_cell(harness.bench_spec(), "no-such-cell")


@pytest.mark.parametrize("cell", ["replay-lagged", "sequence-open"])
def test_a_run_refuses_the_cpu_and_prints_no_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=harness.ROOT,
        env=env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_a_run_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    gives no result."""
    root = str(tmp_path)
    _copy_benchmark(root)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "replay-lagged",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
