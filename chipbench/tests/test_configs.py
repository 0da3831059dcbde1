import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
WIDTHS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "hd", "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias",
}
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("entry", BENCHMARK["configs"], ids=lambda e: e["name"])
def test_config_matches_repro_entry_but_for_reduced(entry):
    from repro.configs import get_arch

    conf = json.loads((ROOT / entry["file"]).read_text())
    arch = get_arch(conf["repro_arch"])
    assert set(entry["reduced"]) == set(conf["reduced"])
    for key, field in WIDTHS.items():
        if key in entry["reduced"]:
            assert conf[key] == conf["reduced"][key]["here"]
            assert getattr(arch, field) == conf["reduced"][key]["published"]
        else:
            assert conf[key] == getattr(arch, field), key
    assert conf["source"] == entry["source"]
    for k in ("assumed", "departures", "deployment"):
        assert conf[k]


def test_cells_name_existing_files():
    configs = {c["name"] for c in BENCHMARK["configs"]}
    for w in BENCHMARK["workloads"]:
        assert w["config"] in configs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_sequence_cut_matches_each_cells_traffic():
    """The configuration's ``max_position_embeddings`` is the longest
    sequence its cells run: the longest prompt plus the answer cap."""
    from chipbench import traffic

    confs = {c["name"]: json.loads((ROOT / c["file"]).read_text())
             for c in BENCHMARK["configs"]}
    for w in BENCHMARK["workloads"]:
        mix = traffic.load_mix(w["traffic"])
        conf = confs[w["config"]]
        assert mix.prompt_max + mix.output_cap == mix.max_len
        assert mix.max_len <= conf["max_position_embeddings"]
