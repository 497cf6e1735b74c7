"""Cells of the benchmark, found by name.

A cell (one entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Each lives in a file of its own, found by
that name: ``configs/<config>.json`` and ``traffic/<traffic>.json``.  Adding
a cell therefore adds files and entries and edits none.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
CONFIG_DIR = os.path.join(HERE, "configs")
TRAFFIC_DIR = os.path.join(HERE, "traffic")

# published config key -> program ArchConfig attribute, for the widths that
# must agree between the file and the program's config
WIDTH_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "resolved_head_dim",
    "vocab_size": "vocab",
    "num_hidden_layers": "n_layers",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


class CellError(RuntimeError):
    pass


def load_benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(directory: str, name: str, what: str) -> dict:
    path = os.path.join(directory, name + ".json")
    if not os.path.isfile(path):
        raise CellError(f"no {what} file {path} for {name!r}")
    with open(path) as f:
        return json.load(f)


def load_config(name: str, directory: str = CONFIG_DIR) -> dict:
    return _load_json(directory, name, "configuration")


def load_traffic(name: str, directory: str = TRAFFIC_DIR) -> dict:
    return _load_json(directory, name, "traffic")


@dataclass(frozen=True)
class Geometry:
    """The engine's shape in one cell."""
    max_batch: int
    max_seq: int
    page: int
    pool_slots: int

    @property
    def max_pages(self) -> int:
        return -(-self.max_seq // self.page)


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int

    @property
    def geometry(self) -> Geometry:
        t, e = self.traffic, self.config["engine"]
        return Geometry(max_batch=int(t["clients"]),
                        max_seq=max(t["prompt_lengths"]) + t["output_range"][1],
                        page=int(e["page"]), pool_slots=int(e["pool_slots"]))


def find_cell(name: str, bench: dict | None = None,
              config_dir: str = CONFIG_DIR,
              traffic_dir: str = TRAFFIC_DIR) -> Cell:
    bench = load_benchmark() if bench is None else bench
    for w in bench["workloads"]:
        if w["name"] == name:
            return Cell(name, load_config(w["config"], config_dir),
                        load_traffic(w["traffic"], traffic_dir),
                        int(w["chips"]))
    raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[w['name'] for w in bench['workloads']]}")


def program_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file: the arch it
    names, with the file's overrides, checked against every width the file
    states."""
    from repro.configs import get_arch, replace
    prog = config["program"]
    cfg = replace(get_arch(prog["arch"]), **prog.get("override", {}))
    for key, attr in WIDTH_KEYS.items():
        if key in config and getattr(cfg, attr) != config[key]:
            raise CellError(f"{config['name']}: program {attr}="
                            f"{getattr(cfg, attr)!r} but the file states "
                            f"{key}={config[key]!r}")
    return cfg
