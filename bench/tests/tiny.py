"""A copy of the benchmark at a size the CPU runs in seconds, for the tests:
the benchmark's own files, the program under ``src/`` linked in, and a tiny
configuration, traffic mix and cell added by name."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "d_ff": 128, "vocab": 512, "rope_theta": 1000000.0}


#: limits at this size, set as the cells' are: above the largest reading of
#: sound runs (loss 4.6e-4, grad 5.6e-3, change 2.8e-3, sign 3.4e-3 over 5
#: seeds) and below the fp8 control's (sign 0.037), half a batch's (grad
#: 0.60), wrong client keys' (sign 0.42) and a flipped update's (sign 0.40)
TINY_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.03, "change_gap": 0.03,
               "sign_gap": 0.012}


def tiny_config(name: str = "tiny-qwen2") -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / "qwen2-0.5b.json")
                     .read_text())
    cfg.update(name=name, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=2, vocab_size=512,
               attention_multiplier=0.25)
    cfg["program"] = {"registry": "qwen2_0_5b", "model": dict(TINY_MODEL)}
    return cfg


def tiny_traffic(clients: int = 4, cohort: str = "stream(shard=1)") -> dict:
    return {"what": "a tiny round for the tests",
            "train_args": {"pipeline": "zsign_packed(z=1,sigma=0.01)",
                           "clients": clients, "groups": 1, "local-steps": 2,
                           "micro-batch": 1, "seq-len": 16,
                           "cohort": cohort, "participation": 1.0,
                           "client-lr": 0.05, "server-lr": 0.5}}


def add_cell(root: Path, cell: str, config: dict, traffic: str, *,
             limits=None, chips: int = 1) -> None:
    """Add a configuration and a cell on the mix ``traffic`` to the
    checkout at ``root`` as new files and entries of its BENCHMARK.json,
    as a later change to the benchmark would."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (root / "bench" / "limits" / f"{cell}.json").write_text(
        json.dumps({"limits": TINY_LIMITS if limits is None else limits}))
    bench["configs"].append({"name": config["name"], "source": "test",
                             "file": f"bench/configs/{config['name']}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": config["name"],
                               "traffic": traffic, "chips": chips,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def make_root(tmp: Path, *, limits=None, chips: int = 1,
              cohort: str = "stream(shard=1)", clients: int = 4) -> Path:
    """A checkout in ``tmp`` holding the cell ``tiny.mix`` and the
    benchmark's files."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "src").symlink_to(ROOT / "src")
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    (tmp / "bench" / "traffic" / "mix.json").write_text(
        json.dumps(tiny_traffic(clients, cohort)))
    add_cell(tmp, "tiny.mix", tiny_config(), "mix", limits=limits,
             chips=chips)
    return tmp
