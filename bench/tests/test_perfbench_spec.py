"""Configs, mixes and metrics are found by name: a new one is a file and
an entry, with no edit to the harness."""
from __future__ import annotations

import json

import perfbench_tiny as T
import spec as S


def _doc():
    return json.loads((T.REPO / "BENCHMARK.json").read_text())


def test_every_named_file_exists_and_loads():
    sp = S.Spec()
    for w in sp.doc["workloads"]:
        assert sp.config(w["config"])["num_layers"] >= 1
        mix = sp.mix(w["traffic"])
        assert (T.BENCH / "drivers" / f"{mix['driver']}.py").is_file()
        assert sp.limits(w["name"])
        for m in sp.per_layer(w["name"]):
            assert callable(sp.metric_reader(m["name"]).read)
    for m in sp.doc["per_layer"]:
        assert sp.metric_reader(m["name"]).SOURCE == m["source"]


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "mixes", "metrics", "limits"):
        (bench / d).mkdir(parents=True)
    doc = _doc()
    doc["configs"].append({"name": "toy", "source": "https://example.org",
                           "file": "bench/configs/toy.json", "reduced": [],
                           "why": "toy"})
    doc["workloads"].append({"name": "toy.burst", "config": "toy",
                             "traffic": "burst", "chips": 1, "why": "toy"})
    doc["per_layer"].append({"name": "toy.share", "unit": "%",
                             "better": "lower", "source": "program_counter",
                             "layer": "device", "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    (bench / "configs" / "toy.json").write_text('{"num_layers": 3}')
    (bench / "mixes" / "burst.json").write_text('{"driver": "toy"}')
    (bench / "limits" / "toy.burst.json").write_text('{"logit_gap": {}}')
    (bench / "metrics" / "toy.share.py").write_text(
        "SOURCE = 'program_counter'\n\ndef read(ctx):\n    return 7.0\n")
    sp = S.Spec(tmp_path, bench)
    assert sp.config("toy") == {"num_layers": 3}
    assert sp.mix("burst") == {"driver": "toy"}
    assert sp.limits("toy.burst") == {"logit_gap": {}}
    assert sp.metric_reader("toy.share").read(None) == 7.0
    # no workloads key: reported wherever the moved metric is
    assert "toy.share" in [m["name"] for m in sp.per_layer("toy.burst")]
    assert "toy.share" in [m["name"] for m in sp.per_layer("sc2-4l.mt-zipf")]


def test_metric_lists_follow_workloads_keys():
    sp = S.Spec()
    e2e = {c: {m["name"] for m in sp.end_to_end(c)}
           for c in ("sc2-4l.mt-zipf", "dsc33-2l.switch-groups")}
    assert e2e["sc2-4l.mt-zipf"] == {"tokens_per_s", "setup_s"}
    assert e2e["dsc33-2l.switch-groups"] == {
        "tokens_per_s", "itl_p95_ms", "ttft_p95_ms", "switch_p95_ms",
        "setup_s"}
    for c, names in e2e.items():
        for m in sp.per_layer(c):
            assert m["moves"] in names, (c, m["name"])
