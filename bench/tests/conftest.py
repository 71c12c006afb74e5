"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Four virtual CPU devices stand in for a four-chip host; Pallas kernels
run in interpret mode. Not part of the repository's tier-1 suite.
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

# paper Table 3's smoke problem (``ct_paper.smoke_problem()``: 8 views of
# a 24^2 detector into a 16^3 volume) and a fleet version with 16 views
TINY = {"vol": 16, "det": 24, "n_proj": 8, "sad": 1000.0, "sdd": 1536.0,
        "extent": 256.0, "det_margin": 1.25}
TINY_FLEET = dict(TINY, n_proj=16)
TINY_MIX = {"pool": 2, "coarse": 8, "check_voxels": 512}


def tiny_config(scan, options, control):
    return {"name": "tiny", "scan": scan, "options": options,
            "control": control, "limits": {"rel_rmse": 1e-5},
            "reduced": [], "assumed": {}}


TINY_CELLS = {
    "tiny.fdk": tiny_config(TINY, {"variant": "subline_pl"},
                            {"program_options": {"precision": "bf16"}}),
    "tiny.fleet4": tiny_config(
        TINY_FLEET, {"variant": "subline_pl", "tiling": [8, 8, 16],
                     "proj_batch": 8, "out": "host", "schedule": "step",
                     "devices": "all"},
        {"reference_store": "bfloat16"}),
}


def make_root(tmp, cells=TINY_CELLS, mix=TINY_MIX):
    """A checkout root whose BENCHMARK.json holds ``cells``: the real
    ``bench/`` directory plus one configuration file per cell."""
    root = str(tmp)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "bench", "mixes", "tiny.json"), "w") as f:
        json.dump(mix, f)
    for name, config in cells.items():
        path = f"bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(config, f)
        spec["configs"].append({"name": name, "source": "test",
                                "file": path, "reduced": [], "why": "test"})
        chips = 4 if config["options"].get("devices") == "all" else 1
        spec["workloads"].append({"name": name, "config": name,
                                  "traffic": "tiny", "chips": chips,
                                  "why": "test"})
        for m in spec["per_layer"]:
            m.setdefault("workloads", []).append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root"))
