"""What one run is told: ``BENCHMARK.json``, the cell it names, and the
cell's configuration, traffic and limit files.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes as run, with their source
    bench/traffic/<traffic>.json    parameters for a generator module
                                    named in the file (bench/traffic/)
    bench/limits/<cell>.json        the limits that decide ``correct``
    bench/metrics/<metric>.py       one reader per metric
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path
from typing import Optional

#: the checkout's root (this file is bench/harness/spec.py)
ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"

#: configuration-file keys (published names) -> the program's
#: ``ModelConfig`` fields
HF_TO_MODEL = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

#: tiny widths for the CPU rehearsal (never a device number)
REHEARSAL_SIZES = {"num_hidden_layers": 2, "hidden_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 32, "intermediate_size": 256,
                   "vocab_size": 512}
REHEARSAL_SERVE = {"slots": 4, "max_len": 256}


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<cell>.json
    end_to_end: list      # metric entries this cell reports at --trace 0
    per_layer: list       # metric entries this cell reports at --trace 1
    run_seconds: int


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Read ``BENCHMARK.json`` and the files of the cell ``name``;
    raises ``KeyError`` for a name it does not list."""
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf_entry = configs[w["config"]]
    bench = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], traffic_name=w["traffic"],
        config=_read_json(root / conf_entry["file"]),
        traffic=_read_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        run_seconds=int(spec["run_seconds"]))


def sizes(config: dict, rehearse: bool = False) -> dict:
    """The configuration's published-name sizes as run (the rehearsal
    swaps in tiny widths)."""
    out = dict(config)
    if rehearse:
        out.update(REHEARSAL_SIZES)
    return out


def serve_settings(config: dict, rehearse: bool = False) -> dict:
    out = dict(config["serve"])
    if rehearse:
        out.update(REHEARSAL_SERVE)
    return out


def model_config(config: dict, rehearse: bool = False):
    """The program's ``ModelConfig`` for a configuration file: every
    size comes from the file, nothing from the program's registry."""
    from repro.configs.base import ModelConfig

    s = sizes(config, rehearse)
    if s["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {s['hidden_act']!r}")
    kw = {field: s[key] for key, field in HF_TO_MODEL.items()}
    return ModelConfig(name=config["name"] + ("-rehearsal" if rehearse
                                              else ""),
                       family=config["family"], mlp_act="swiglu",
                       qk_norm=bool(s.get("qk_norm", False)),
                       dtype=s["torch_dtype"], **kw)


def metric_module_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "metrics" / f"{name}.py"


def traffic_module_path(generator: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{generator}.py"


def load_module(path: Path, modname: Optional[str] = None):
    """Import one reader or generator file by path."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        modname or f"bench_{path.stem.replace('-', '_').replace('.', '_')}",
        path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
