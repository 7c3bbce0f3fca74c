"""A new configuration, traffic mix, cell and per-layer metric are found
from new files alone; BENCHMARK.json and the data files are consistent."""
import json
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from serve_bench import spec, traffic  # noqa: E402


def test_every_cell_loads_with_its_metrics():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.load_metric(m["name"]))
            assert m["moves"] in names
        assert cell.config["reduced"] == []
        assert set(cell.workload["check"]["limits"]) <= {"max_gap",
                                                         "mean_gap"}
        traffic.Traffic(cell.mix, 1, cell.config["vocab_size"],
                        cell.engine["max_seq"])


def test_config_files_match_the_registry():
    """The file is what runs: its sizes are the program's registry entry."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config

    for c in spec.load_benchmark()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        m = get_config(cfg["registry"])
        assert (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads, m.d_ff,
                m.vocab_size, m.hd, m.tie_embeddings) == (
            cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["head_dim"],
            cfg["tie_word_embeddings"])


def test_new_pieces_are_files_only(tmp_path):
    root = tmp_path / "checkout"
    here = root / "serve_bench"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    cfg = json.loads((here / "configs" / "granite-3-2b.json").read_text())
    cfg["num_hidden_layers"] = 4
    (here / "configs" / "tiny-granite.json").write_text(json.dumps(cfg))
    (here / "traffic" / "bursty.json").write_text(json.dumps({
        "arrival": "gamma", "cv": 3.0,
        "prompt": {"dist": "uniform", "min": 10, "max": 20},
        "output": {"dist": "fixed", "value": 3, "min": 2, "max": 4}}))
    # a new arrival process and a new length law, each a file of its own
    (here / "traffic" / "arrivals" / "gamma.py").write_text(
        "import numpy as np\n"
        "from serve_bench import traffic as tr\n\n\n"
        "def source(traffic, workload, lead_s, window_s):\n"
        "    k = 1.0 / traffic.mix['cv'] ** 2\n"
        "    gaps = lambda n: np.random.default_rng(n).gamma(k, 1 / k, n)\n"
        "    return tr.Schedule(traffic.open_loop(\n"
        "        workload['rate_per_s'], lead_s, window_s, gaps))\n")
    (here / "traffic" / "lengths" / "fixed.py").write_text(
        "import numpy as np\n\n\n"
        "def inverse_cdf(dist, u):\n"
        "    return np.full(len(u), dist['value'])\n")
    (here / "workloads" / "tiny-granite.bursty.json").write_text(json.dumps({
        "engine": {"slots": 2, "max_seq": 64}, "rate_per_s": 3.0,
        "lead_in_s": 1, "drain_s": 5,
        "check": {"requests": 2, "limits": {"max_gap": 1.0}}}))
    (here / "metrics" / "ticks_seen.py").write_text(
        "def read(rec):\n    return float(len(rec['ticks'])) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-granite.bursty",
                               "config": "tiny-granite", "traffic": "bursty",
                               "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "ticks_seen", "unit": "ticks",
                               "better": "higher", "source": "program_span",
                               "layer": "host per-tick",
                               "moves": "itl_p50_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny-granite.bursty", root=str(root),
                          here=str(here))
    assert cell.config["num_hidden_layers"] == 4
    assert cell.mix["prompt"]["max"] == 20
    assert cell.workload["rate_per_s"] == 3.0
    # a metric with no cell list is read in every cell reporting its
    # end-to-end metric, the new cell and the old ones alike
    assert "ticks_seen" in [m["name"] for m in cell.per_layer]
    old = spec.load_cell("granite-3-2b.decode", root=str(root),
                         here=str(here))
    assert "ticks_seen" in [m["name"] for m in old.per_layer]
    read = spec.load_metric("ticks_seen", here=str(here))
    assert read({"ticks": [1, 2, 3]}) == 3.0 and read({"ticks": []}) is None
    src = traffic.Traffic(cell.mix, 9, cfg["vocab_size"], 64,
                          here=str(here / "traffic")).source(
        cell.workload, 1.0, 10.0)
    reqs = [r for r, _ in src.take(1e9)]
    assert len(reqs) == 33 and not src.closed
    assert all(10 <= len(r.prompt) <= 20 and r.max_new == 3 for r in reqs)
    gaps = np.diff([r.due for r in reqs[3:]])
    assert np.all(gaps > 0)
    assert gaps.std() / gaps.mean() > 1.5      # burstier than Poisson


def test_unknown_device_kind_is_an_error():
    import pytest

    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        spec.load_peaks("TPU v9 imaginary")
