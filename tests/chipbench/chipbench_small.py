"""Small cells for the benchmark's CPU tests: the published configurations'
architectures at tiny widths, in bfloat16 as served, with a short traffic
mix."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             intermediate_size=128, vocab_size=256, head_dim=16)
REPO_KEYS = dict(num_hidden_layers="num_layers", hidden_size="d_model",
                 num_attention_heads="num_heads", num_key_value_heads="num_kv_heads",
                 intermediate_size="d_ff", vocab_size="vocab_size", head_dim="head_dim",
                 num_experts="num_experts", num_experts_per_tok="experts_per_token")

TRAFFIC = {"kind": "waves", "slots": 4, "max_len": 32, "sizes_seed": 0,
           "prompt": {"mean": 8, "min": 4, "max": 14},
           "gen": {"mean": 6, "min": 2, "max": 12}}


def sizes(name, **extra):
    """A tiny version of configuration ``name``'s file, with the repo
    overrides that make the program's config agree with it."""
    s = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    grouped = s["num_key_value_heads"] < s["num_attention_heads"]
    small = dict(SMALL, num_key_value_heads=2 if grouped else 4)
    if "num_experts" in s:
        small.update(num_experts=8, num_experts_per_tok=2)
    small.update(extra)
    s.update(small)
    over = dict(s["repo"]["overrides"])
    over.update({REPO_KEYS[k]: v for k, v in small.items()})
    if "num_experts" in s:
        over["moe_capacity_factor"] = s["num_experts"] / s["num_experts_per_tok"]
    s["repo"] = dict(s["repo"], overrides=over)
    return s


def bench(cell="small"):
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    return b, {"name": cell, "chips": 1}
