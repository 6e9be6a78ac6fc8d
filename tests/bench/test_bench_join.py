"""A model kind that brings its own Pallas kernel, its own per-layer
metric and its own model key joins the benchmark by new files alone: on
a copy of the benchmark with such a kind added, and no file of it
edited, the benchmark's tests of its files, counts and check pass."""
import json
import os
import shutil
import subprocess
import sys

from bench_tiny_root import REPO

KIND = "joiner"
KERNEL = "joiner_aggregate"
METRIC = f"{KERNEL}_roofline"
CELL = f"{KIND}-flickr.zipf"
TESTS = ("test_bench_flops.py", "test_bench_discovery.py",
         "test_bench_check.py")

KERNEL_FILE = '''"""A kernel of the joining kind: one pass over h; it needs h and its
output."""


def count(operands, out, model):
    c, n, f = operands[0][0]
    return 2.0 * c * n * f, [out, operands[0]]
'''

METRIC_FILE = f'''"""The joining kind's kernel: least time on the chip for its traced
calls over their measured device time."""
from bench import tracing

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"
BETTER = "higher"
KERNEL = "{KERNEL}"
MODEL_NEEDS = ("AGGREGATORS",)


def read(run):
    if run.trace is None:
        return None
    return tracing.kernel_roofline(run.trace, KERNEL,
                                   run.cell.model_module(), run.peaks)
'''


def _write(path, text):
    assert not os.path.exists(path), path      # new files alone
    with open(path, "w") as f:
        f.write(text)


def test_a_kind_with_its_own_kernel_metric_and_key_joins_by_new_files(
        tmp_path):
    root = os.path.join(str(tmp_path), "checkout")
    for part in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(REPO, part), os.path.join(root, part),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    bench = os.path.join(root, "bench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)

    # the kind: the GraphSAGE module with an attribute no other kind has
    with open(os.path.join(REPO, "tests", "bench", "sage_model.py")) as f:
        model = f.read()
    _write(os.path.join(bench, "models", f"{KIND}.py"),
           model + '\n\nAGGREGATORS = ("mean",)\n')
    _write(os.path.join(bench, "kernels", f"{KERNEL}.py"), KERNEL_FILE)
    _write(os.path.join(bench, "metrics", f"{METRIC}.py"), METRIC_FILE)

    # its configuration sets a GNNConfig field the others leave at its
    # default
    first = bm["configs"][0]
    with open(os.path.join(REPO, first["file"])) as f:
        config = json.load(f)
    for c in bm["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            assert "name" not in json.load(f)["model"], c["name"]
    config["name"] = f"{KIND}-flickr"
    config["model"].update(kind=KIND, name=KIND)
    _write(os.path.join(bench, "configs", f"{KIND}-flickr.json"),
           json.dumps(config))
    bm["configs"].append(dict(first, name=f"{KIND}-flickr",
                              file=f"bench/configs/{KIND}-flickr.json"))
    bm["workloads"].append({"name": CELL, "config": f"{KIND}-flickr",
                            "traffic": bm["workloads"][0]["traffic"],
                            "chips": 1, "why": "a kind that joins"})
    bm["per_layer"].append({"name": METRIC, "unit": "%", "better": "higher",
                            "source": "device_trace", "layer": "kernels",
                            "moves": "latency_p50_ms", "workloads": [CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "xdist", "-n", "0",
         "-p", "no:cacheprovider", "-p", "no:randomly"]
        + [os.path.join("tests", "bench", t) for t in TESTS],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-6000:]
    for test in ("test_every_cell_resolves_its_files",
                 "test_program_configs_are_built_as_before"):
        assert f"{test}[{CELL}] PASSED" in out, test
    assert "test_kernels_are_the_count_files PASSED" in out
