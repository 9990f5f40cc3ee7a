"""Tiny cells for running the harness on the CPU: the real cells'
traffic and limits, with the model shrunk and the sizes cut so that a
run takes seconds."""
import copy

from bench import harness

TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256}
# wide and deep enough on the CPU for bf16 and float8 rounding to flip
# different shares of greedy tokens
WIDER_MODEL = {"num_hidden_layers": 6, "hidden_size": 256,
               "intermediate_size": 768, "num_attention_heads": 8,
               "num_key_value_heads": 4, "head_dim": 32, "vocab_size": 16384}
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "intermediate_size": "d_ff", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "vocab_size": "vocab_size"}


def tiny_config(name: str, model: dict = TINY_MODEL) -> dict:
    cfg = copy.deepcopy(harness.load_json(
        harness.BENCH / "configs" / f"{name}.json"))
    cfg["published"].update(model)
    cfg["overrides"].update({FIELDS[k]: v for k, v in model.items()})
    return cfg


def tiny_cell(cell: str, model: dict = TINY_MODEL) -> harness.Cell:
    w = {x["name"]: x for x in harness.benchmark()["workloads"]}[cell]
    cfg = tiny_config(w["config"], model)
    tr = copy.deepcopy(harness.load_json(
        harness.BENCH / "traffic" / f"{w['traffic']}.json"))
    if tr["driver"] == "train":
        tr.update(seq=32, batch=4)
        if "deploys" in tr:
            tr["deploys"].update(first_s=0.5, every_s=1.5)
    else:
        tr.update(batch=4, prompt_len=16, new_tokens=8, max_seq=24,
                  check_rows=2)
        if "deploys" in tr:
            tr["deploys"].update(first_s=0.3, every_s=1.0)
    return harness.load_cell(cell, config=cfg, traffic=tr)
