import json
from pathlib import Path

import pytest

from chipbench import harness, refmodels

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
# every configuration file: the benchmark's and the tests'
CONFIG_FILES = [ROOT / c["file"] for c in BENCHMARK["configs"]] + sorted(
    p for p in DATA.glob("*.json") if "repro_arch" in json.loads(p.read_text()))
# (configuration, published key) where the program's named entry holds
# another value than the published one the file sets
ENTRY_DIFFERS = {
    # the program's entry keeps ArchConfig's default 1e-5; the published
    # config says 1e-6, which the harness applies
    ("qwen2-1.5b", "rms_norm_eps"),
}


def _winning_keys(conf):
    """field -> the published key that sets it (the table's last one the
    file has)."""
    return {f: k for k, f in harness.ARCH_FIELDS.items() if k in conf}


@pytest.mark.parametrize("entry", BENCHMARK["configs"], ids=lambda e: e["name"])
def test_config_matches_repro_entry_but_for_reduced(entry):
    from repro.configs import get_arch

    conf = json.loads((ROOT / entry["file"]).read_text())
    arch = get_arch(conf["repro_arch"])
    assert set(entry["reduced"]) == set(conf["reduced"])
    for field, key in _winning_keys(conf).items():
        # ArchConfig's head_dim 0 means d_model // n_heads; hd resolves it
        have = getattr(arch, "hd" if field == "head_dim" else field)
        if key in entry["reduced"]:
            assert conf[key] == conf["reduced"][key]["here"]
            assert have == conf["reduced"][key]["published"]
        elif (entry["name"], key) not in ENTRY_DIFFERS:
            assert conf[key] == have, key
    assert conf["source"] == entry["source"]
    for k in ("assumed", "departures", "deployment"):
        assert conf[k]


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_names_a_reference_model_and_known_program_fields(path):
    import dataclasses

    from repro.configs.base import ArchConfig

    conf = json.loads(path.read_text())
    assert conf["reference"] in refmodels.names()
    assert (refmodels.DIR / f"{conf['reference']}.py").is_file()
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    explained = set(conf.get("reduced", {})) | set(conf.get("assumed", {}))
    for field in conf.get("program", {}):
        assert field in fields, field
        assert field in explained, field


def _write(tmp_path, **changes):
    conf = json.loads((DATA / "tiny.json").read_text())
    conf.update(changes)
    conf = {k: v for k, v in conf.items() if v is not None}
    (tmp_path / "c.json").write_text(json.dumps(conf))
    return tmp_path


@pytest.mark.parametrize("reference,message", [
    (None, "names no reference model"),
    ("dense", "unknown reference model 'dense'"),
    ("../check", "unknown reference model"),
])
def test_config_without_a_known_reference_is_refused(tmp_path, reference,
                                                     message):
    d = _write(tmp_path, reference=reference)
    with pytest.raises(ValueError, match=message):
        harness.load_config("c", d)


def test_program_block_sets_fields_after_the_published_keys(tmp_path):
    d = _write(tmp_path, repro_arch="qwen3-30b-a3b", moe_intermediate_size=32,
               num_experts_per_tok=2, program={"n_experts": 4, "top_k": 3})
    cfg, _ = harness.load_config("c", d)
    # moe_intermediate_size wins over intermediate_size; program over both
    assert (cfg.d_ff, cfg.n_experts, cfg.top_k) == (32, 4, 3)
    assert cfg.d_model == 64 and cfg.family == "moe"


def test_program_field_the_program_lacks_is_refused(tmp_path):
    d = _write(tmp_path, program={"experts_held": 8})
    with pytest.raises(ValueError, match=r"\['experts_held'\] are not fields"):
        harness.load_config("c", d)


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
