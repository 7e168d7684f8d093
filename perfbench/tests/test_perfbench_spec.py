"""Finding cells, configurations, the mix, the references and the
per-layer readers by name, and the shape of BENCHMARK.json."""
import json
import os
import re
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import flops, spec  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_parts():
    for cell in BENCH["workloads"]:
        w = spec.workload(BENCH, cell["name"])
        cfg = spec.config(BENCH, w["config"])
        mix = spec.traffic(w["traffic"])
        ref = spec.reference(cfg)
        assert hasattr(ref, "served_logits")
        assert cfg["serving"]["capacity"] == cfg["model"]["max_seq_len"]
        assert {"max_logit_gap", "min_tokens_compared"} <= set(cfg["checks"])
        assert mix["users"] == "rows"


def test_configs_hold_the_published_sizes_unreduced():
    for c in BENCH["configs"]:
        cfg = spec.config(BENCH, c["name"])
        pub, m = cfg["published"], cfg["model"]
        assert c["source"] == cfg["source"] and c["reduced"] == []
        assert m["num_layers"] == pub["n_layer"]
        assert m["d_model"] == pub["n_embd"]
        assert m["num_heads"] == m["num_kv_heads"] == pub["n_head"]
        assert m["d_ff"] == 4 * pub["n_embd"]
        assert m["vocab_size"] == pub["vocab_size"] == 50257
        assert m["max_seq_len"] == pub["n_positions"] == 1024
        assert m["head_dim"] * m["num_heads"] == m["d_model"]


def test_every_per_layer_metric_has_a_reader_that_reads_nothing_empty():
    empty = SimpleNamespace(
        counters0={"decode_steps": 0, "occupancy_sum": 0,
                   "engine.tokens_reused": 0, "engine.tokens_prefilled": 0},
        counters1={"decode_steps": 0, "occupancy_sum": 0,
                   "engine.tokens_reused": 0, "engine.tokens_prefilled": 0},
        trace=None, decode_ctx=[], admissions=[],
        dims=flops.Dims(1, 64, 4, 4, 16, 256, 512),
        peaks=flops.peaks("TPU v5 lite"))
    ends = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in ends
        assert spec.metric_reader(m["name"]).read(empty) is None


def test_benchmark_file_keeps_the_contracts_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    names = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"}),
                        ("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"})):
        for e in BENCH[group]:
            assert set(e) <= keys | {"workloads"} and keys - {"workloads"} \
                <= set(e), (group, e)
            assert NAME.match(e["name"]) and e["name"] not in names
            names.add(e["name"])
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(BENCH)) < 64 * 1024
