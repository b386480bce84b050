"""A checkout root for CPU rehearsals: the repo's ``BENCHMARK.json`` and
``chipbench/`` copied, plus smoke-size cells added as new files and new
entries only (the registry's ``SMOKE`` configs through the same path)."""

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SMOKE_PHI3 = {
    "registry": "phi3-mini-3.8b", "smoke": True, "source": "registry SMOKE",
    "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 16, "d_ff": 128, "vocab": 128,
    "act": "swiglu", "norm": "rmsnorm", "norm_eps": 1e-5,
    "rope_theta": 10000.0, "tie_embeddings": False, "dtype": "bfloat16",
    "n_slots": 4, "cache_len": 128, "policy": "dlbc"}
SMOKE_MINITRON = dict(SMOKE_PHI3, registry="minitron-4b", n_kv_heads=2,
                      vocab=256)
LENGTHS = {"prompt_len": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                          "min": 4, "max": 80},
           "output_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                          "min": 8, "max": 40}}
#: widest logit gap allowed in a smoke run.  CPU readings over 6 seeds
#: per smoke cell (``chipbench/control.py`` at 1.5 s): the program 0.018
#: to 0.048, the float8 control 0.21 to 1.14.  The limit lies between,
#: with more room above the program's largest reading.
SMOKE_LIMIT = 0.12


def add_cell(root: Path, name: str, config_name: str, config: dict,
             traffic_name: str, traffic: dict, limit: float = SMOKE_LIMIT):
    """A cell from new files and new ``BENCHMARK.json`` entries only."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cb = root / "chipbench"
    (cb / "configs" / f"{config_name}.json").write_text(json.dumps(config))
    (cb / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    (cb / "limits" / f"{name}.json").write_text(
        json.dumps({"max_logit_gap": limit}))
    bench["configs"].append({"name": config_name, "source": "registry SMOKE",
                             "file": f"chipbench/configs/{config_name}.json",
                             "reduced": [], "why": "CPU rehearsal"})
    bench["workloads"].append({"name": name, "config": config_name,
                               "traffic": traffic_name, "chips": 1,
                               "why": "CPU rehearsal"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


def list_cell_in(root: Path, cell: str, like: str):
    """Make ``cell`` report every metric that lists ``like``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    add_cell(root, "phi3-smoke-poisson", "phi3-smoke", SMOKE_PHI3,
             "smoke-poisson",
             dict(LENGTHS, driver="open_loop", schedule_seed=0, rate_rps=40.0,
                  drain_s=30))
    list_cell_in(root, "phi3-smoke-poisson", "phi3-mixed-poisson")
    add_cell(root, "minitron-smoke-backlog", "minitron-smoke",
             SMOKE_MINITRON, "smoke-backlog",
             dict(LENGTHS, driver="backlog", schedule_seed=0, requests=64))
    list_cell_in(root, "minitron-smoke-backlog", "phi3-decode-backlog")
    return root
