"""run.py --rehearse on the CPU: the last line's shape for every committed
cell, errors that name what is unknown, and a wrong answer that is caught.

Run by hand (minutes: every cell runs twice at SF0.01):
    python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def rehearse(root, cell, trace, seconds=3, seed=2**31 + 7):
    """-> (return code, last line of stdout parsed or None, stderr)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "BENCH_RUN": "the driver's own; the benchmark takes no notice"}
    p = subprocess.run(
        [sys.executable, os.path.join(root, *BENCHMARK["command"][1].split("/")),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p.returncode, json.loads(lines[-1]) if lines and p.returncode == 0 \
        else None, p.stderr


def listed(kind, cell):
    return {m["name"]: m["unit"] for m in BENCHMARK[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line(cell, trace):
    with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
        slice_s = json.load(f)["trace_seconds"]
    # host metrics of a traced run come from the queries after the slice
    rc, line, err = rehearse(ROOT, cell, trace, seconds=slice_s + 5 if trace else 3)
    assert rc == 0, err[-2000:]
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if trace else keys)
    assert line["correct"] is False         # a rehearsal is never a result
    assert line["attempted"] > 0 and line["failed"] == 0
    want = listed("per_layer" if trace else "end_to_end", cell)
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    # a CPU reports no memory, so that one reader finds nothing to read
    assert got == {k: v for k, v in want.items() if k in got}
    assert set(want) - set(got) <= {"peak_hbm_gb"}
    for m in line["metrics"].values():
        assert isinstance(m["value"], (int, float))
    dev = set(line["device"])
    assert dev == (DEVICE_KEYS | {"busy_s", "window_s"} if trace else DEVICE_KEYS)
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        for rows in line["breakdown"].values():
            assert 0 < len(rows) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in rows)


def test_metric_files_say_what_benchmark_json_says():
    sys.path.insert(0, BENCH)
    import run

    for m in BENCHMARK["per_layer"]:
        mod = run.metric_module("per_layer", m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
    for m in BENCHMARK["end_to_end"]:
        mod = run.metric_module("end_to_end", m["name"])
        assert (mod.NAME, mod.UNIT) == (m["name"], m["unit"])


def test_no_cell_query_or_metric_is_named_in_run_py():
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    names = (CELLS + [c["name"] for c in BENCHMARK["configs"]]
             + [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
             + [f[:-4] for f in os.listdir(os.path.join(BENCH, "queries"))])
    assert [n for n in names if re.search(rf"\b{re.escape(n)}\b", text)] == []


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """BENCHMARK.json and benchmarks/ alone, to be broken at will."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__", "tests"))
    return root


def edit_json(path, change):
    with open(path) as f:
        d = json.load(f)
    change(d)
    with open(path, "w") as f:
        json.dump(d, f)


@pytest.mark.parametrize("what, change", [
    ("no_such_config", lambda w: w.update(config="no_such_config")),
    ("no_such_check", lambda w: w["classes"][0].update(check="no_such_check")),
    ("no_such_binds", lambda w: w["classes"][0].update(binds="no_such_binds")),
    ("no_such_query", lambda w: w["classes"][0].update(query="no_such_query")),
])
def test_unknown_names_are_named(copy, what, change):
    src = copy / "benchmarks" / "workloads" / f"{CELLS[0]}.json"
    dst = copy / "benchmarks" / "workloads" / f"broken_{what}.json"
    shutil.copy(src, dst)
    edit_json(dst, change)
    rc, line, err = rehearse(str(copy), f"broken_{what}", 0)
    assert rc != 0 and line is None
    assert what in err.strip().splitlines()[-1]


def test_unknown_workload_and_layer_metric_are_named(copy):
    rc, line, err = rehearse(str(copy), "no_such_cell", 0)
    assert rc != 0 and line is None and "no_such_cell" in err.splitlines()[-1]
    other = copy.parent / "with_unknown_metric"
    shutil.copytree(copy, other)
    edit_json(other / "BENCHMARK.json", lambda b: b["per_layer"].append(
        {"name": "no_such_metric", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "device", "moves": "qps"}))
    rc, line, err = rehearse(str(other), CELLS[0], 1)
    assert rc != 0 and line is None and "no_such_metric" in err.splitlines()[-1]


def test_a_wrong_expected_answer_fails_queries(copy):
    sys.path.insert(0, BENCH)
    import reference

    cache = copy / "benchmarks" / ".cache"
    cache.mkdir(exist_ok=True)
    with open(os.path.join(BENCH, "workloads", f"{CELLS[0]}.json")) as f:
        wl = json.load(f)
    check = next(c["check"] for c in wl["classes"] if c["check"] in reference.STREAMED)
    made = reference.streamed(0.01, [check])[check]
    made[0][-1] = made[0][-1] * 1.01 + 1          # one value, one per cent off
    name = f"ref_{wl['config']}_sf0.01_{check}_{reference.source_hash()}.json"
    with open(cache / name, "w") as f:
        json.dump(made, f)
    rc, line, err = rehearse(str(copy), CELLS[0], 0)
    assert rc == 0, err[-2000:]
    assert line["failed"] > 0 and line["correct"] is False
    assert line["attempted"] > line["failed"]     # the other class is still right


def test_without_the_program_it_fails_and_prints_no_result(copy):
    """In a directory that holds only BENCHMARK.json and benchmarks/."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=copy, env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert not any(l.startswith('{"correct"') for l in p.stdout.splitlines())
