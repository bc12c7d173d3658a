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


def make_root(tmp: Path, *, limits=None, chips: int = 1,
              cohort: str = "stream(shard=1)", clients: int = 4) -> Path:
    """A checkout in ``tmp`` holding the cell ``tiny.mix`` and the
    benchmark's files."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs" / "tiny-qwen2.json").write_text(
        json.dumps(tiny_config()))
    (tmp / "bench" / "traffic" / "mix.json").write_text(
        json.dumps(tiny_traffic(clients, cohort)))
    if limits is None:
        limits = TINY_LIMITS
    (tmp / "bench" / "limits" / "tiny.mix.json").write_text(
        json.dumps({"limits": limits}))
    bench["configs"].append({"name": "tiny-qwen2", "source": "test",
                             "file": "bench/configs/tiny-qwen2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.mix", "config": "tiny-qwen2",
                               "traffic": "mix", "chips": chips,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.mix")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
