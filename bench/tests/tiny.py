"""A tiny cell for driving the harness on the CPU: the smollm
configuration's shape at toy widths, and a 256-token ProLong mix."""
import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def tiny_config(name: str = "smollm-360m", **over):
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=256)
    c.update(over)
    return c


def tiny_mix(**over):
    m = {"distribution": "prolong", "seq_len": 256, "max_doc_len": 256,
         "rows_per_rank": 2, "layouts": 4, "layout_seed": 7}
    m.update(over)
    return m


def tiny_cell(config=None, mix=None, chips=1):
    config = config or tiny_config()
    return {"cell": {"name": "tiny", "config": config["name"],
                     "traffic": "tiny", "chips": chips},
            "config": copy.deepcopy(config), "mix": mix or tiny_mix(),
            "end_to_end": [{"name": "tokens_per_s_per_chip", "unit": "tokens/s"},
                           {"name": "peak_hbm_gib", "unit": "GiB"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}
