"""Small copies of the benchmark's cells for tests on the CPU: the bench
directory copied under a temporary directory, with a configuration and
traffic file at a size a test run can hold added as new files."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path
from typing import Dict

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def spec() -> Dict:
    return load(ROOT / "BENCHMARK.json")


def bench_copy(tmp: Path) -> Path:
    dst = tmp / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    return dst


def add_cell(bench: Path, spec_: Dict, name: str, traffic: Dict,
             config: Dict) -> Dict:
    """Write ``traffic`` and ``config`` as new files; returns a spec in
    which the new cell reports what its model cell reports."""
    with open(bench / "configs" / f"{config['name']}.json", "w") as f:
        json.dump(config, f)
    with open(bench / "traffic" / f"{name}.json", "w") as f:
        json.dump(dict(traffic, name=name, config=config["name"]), f)
    model = traffic["name"]
    out = copy.deepcopy(spec_)
    for m in out["end_to_end"] + out["per_layer"]:
        if model in m.get("workloads", ()):
            m["workloads"].append(name)
    out["workloads"].append({"name": name, "config": config["name"],
                             "traffic": name, "chips": 1, "why": "test"})
    return out


def tiny_query_cell(tmp: Path, name: str = "tiny.q1", *, scale=0.002,
                    partition_rows=4096):
    """The q1 cell at a tenth of a thousandth of its scale."""
    bench = bench_copy(tmp)
    traffic = load(BENCH / "traffic" / "tpch-sf5.q1.json")
    config = load(BENCH / "configs" / "tpch-lineitem-sf5.json")
    config = dict(config, name="tpch-lineitem-tiny", scale_factor=scale,
                  partition_rows=partition_rows,
                  deployment=dict(config["deployment"], percipience=False))
    return bench, add_cell(bench, spec(), name, traffic, config)


def tiny_train_cell(tmp: Path, name: str = "tiny.train", *,
                    compute_dtype="float32", batch=4, seq=64,
                    trace_seconds=None):
    """The training cell at two layers of width 64 and a 256-token
    vocabulary, in float32 unless ``compute_dtype`` says otherwise,
    traced for ``trace_seconds`` of a window (all of it for None)."""
    bench = bench_copy(tmp)
    traffic = load(BENCH / "traffic" / "train.mamba2-130m.json")
    config = load(BENCH / "configs" / "mamba2-130m.json")
    config = dict(config, name="mamba2-tiny", n_layers=2, d_model=64,
                  d_state=16, headdim=16, chunk_size=16, vocab_size=256,
                  train=dict(config["train"], compute_dtype=compute_dtype,
                             microbatch=2))
    traffic = dict(traffic, batch=batch, seq=seq,
                   trace_seconds=trace_seconds)
    return bench, add_cell(bench, spec(), name, traffic, config)
