"""``BENCHMARK.json`` and the files it names: every name resolves to its
own file, and a new cell, mix or metric is new files and entries only."""
import copy
import json
import re

from bench.harness import spec

BENCH = spec.load_json(spec.ROOT + "/BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_resolves_to_a_file():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == 1
        assert spec.harness(cell.traffic).run
        assert spec.adapter(cell.conf).program_params
        assert spec.reference(cell.conf).layer
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(spec.metric_reader(m["name"]).read)


def test_config_files_state_what_they_cut():
    for c in BENCH["configs"]:
        conf = spec.load_json(spec.ROOT + "/" + c["file"])
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert set(conf["published"]) == set(c["reduced"])
        for k in c["reduced"]:
            assert conf[k] != conf["published"][k]


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= set(names)


def test_a_new_cell_is_new_entries_only(tmp_path):
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append({
        "name": "qwen_chat_again", "config": "qwen2_5_3b",
        "traffic": "chat", "chips": 1, "why": "a second chat cell"})
    for m in bench["per_layer"]:
        if m["name"] == "decode_step_ms":
            m["workloads"].append("qwen_chat_again")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.load_cell("qwen_chat_again", str(path))
    assert cell.traffic == spec.load_cell("qwen_chat").traffic
    assert "decode_step_ms" in [m["name"] for m in cell.per_layer]
    assert "engine_host_share" not in [m["name"] for m in cell.per_layer]
