"""BENCHMARK.json and the files it names, against the contract."""

import copy
import json
from pathlib import Path

import pytest

from chipbench import manifest
from chipbench.configs import load_config, model_fields
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = ROOT / "tests" / "chipbench" / "data" / "tiny_manifest.json"


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.mark.parametrize("path", [None, TINY], ids=["BENCHMARK.json", "tiny"])
def test_manifest_is_sound(path):
    assert manifest.problems(manifest.load(path)) == []


def test_file_is_small_and_command_stays_inside_paths(man):
    assert (ROOT / "BENCHMARK.json").stat().st_size < 64 * 1024
    assert man["command"] == ["python3", "-m", "chipbench.run"]
    assert all(not w.startswith("/") and ".." not in w for w in man["command"])


def test_the_whole_file_keeps_the_drivers_rules_of_form(man):
    """What the driver refuses before any run and ``manifest.problems`` does
    not measure; one test for the whole file, whatever cells it holds."""
    assert len(json.dumps(man)) < 64 * 1024
    assert 1 <= len(man["per_layer"]) <= 128 and 1 <= len(man["end_to_end"]) <= 16
    assert 1 <= len(man["configs"]) <= 24 and 1 <= len(man["workloads"]) <= 24
    one_line = lambda s: 1 <= len(s) <= 200 and s.isprintable()   # noqa: E731
    for entry in man["configs"] + man["workloads"]:
        assert all(one_line(entry[key]) for key in ("why", "source") if key in entry), entry
    for c in man["configs"]:
        assert len(c["reduced"]) <= 16 and all(manifest.NAME.match(k) for k in c["reduced"])
    assert len({c["file"] for c in man["configs"]}) == len(man["configs"])
    assert all(one_line(m["layer"]) for m in man["per_layer"])
    assert all(one_line(word) for word in man["command"]) and len(man["command"]) <= 32
    # a roofline share is a percentage, and the whole step's share of the peak
    # stands beside the kernels' (the contract's `mfu`)
    for m in man["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher", m["name"]
    # (the six cells of PR 41 each list it; a later cell brings the share that
    # fits its step, under a name with `mfu` in it)
    for cell in ("qwen7b-decode-batch", "qwen1p5b-chat-steady", "ouro2p6b-reason-decode",
                 "axk1-ep16-decode", "lfm2-24b-hybrid-decode", "laguna-s21-longctx-agents"):
        assert layer_entry(man, "decode_step_mfu", cell) is not None, cell


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader_module(man, kind):
    import importlib

    for m in man[kind]:
        spec = json.loads(manifest.metric_file(kind, m["name"]).read_text())
        module = importlib.import_module(f"chipbench.readers.{spec['reader']}")
        assert callable(module.read), m["name"]
        assert spec["doc"]


def test_layer_metric_moves_a_metric_its_cells_report(man):
    for m in man["per_layer"]:
        for cell in m.get("workloads", [w["name"] for w in man["workloads"]]):
            reported = {e["name"] for e in manifest.metrics_of(man, "end_to_end", cell)}
            assert m["moves"] in reported, (m["name"], cell)


def test_layers_are_spelled_one_way(man):
    # PERF.md section 3's layers, letter for letter; a later PR may name a
    # layer of its own beside them
    layers = {m["layer"] for m in man["per_layer"]}
    assert layers >= {"load generator", "frontend", "scheduler",
                      "device programs", "kernels", "device", "set-up",
                      "demoted end-to-end"}


def test_a_suffixed_metric_reads_with_its_base_names_file():
    base = manifest.metric_file("per_layer", "queue_wait_ms_mean")
    assert manifest.metric_file("per_layer", "queue_wait_ms_mean.chat") == base
    assert manifest.metric_file("per_layer", "device_idle_share.chat").name == (
        "device_idle_share.json")
    own = manifest.metric_file("per_layer", "open_loop_ttft_ms_p90")
    assert own.name == "open_loop_ttft_ms_p90.json" and own.exists()
    assert not manifest.metric_file("per_layer", "nobody.reads_me").exists()


@pytest.mark.parametrize("chips,topology", [(1, "one-worker"), (4, "four-replicas-kv-router")])
def test_topology_follows_from_the_chips(chips, topology):
    assert manifest.topology_of({"name": "any", "chips": chips}) == topology


@pytest.mark.parametrize("fault,needle", [
    (lambda m: m["workloads"][0].update(name="has space"), "is not a name"),
    (lambda m: m["end_to_end"][1].update(unit="tokens per second"), "unit"),
    (lambda m: m["end_to_end"][1].update(bound=0.2), "bound"),
    (lambda m: m["per_layer"][0].update(moves="tpot_ms_p50"), "does not report"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "no traffic file"),
    (lambda m: m["workloads"][0].update(topology="one-worker"), "keys"),
    (lambda m: m["per_layer"][0].update(why="because"), "keys"),
    (lambda m: m.update(metrics=[]), "not exactly"),
    (lambda m: m["end_to_end"].pop(0), "no setup_s"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "four-chip"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="nobody_reads_me")),
     "no reader file"),
])
def test_faults_are_found(man, fault, needle):
    broken = copy.deepcopy(man)
    fault(broken)
    assert any(needle in p for p in manifest.problems(broken)), manifest.problems(broken)


@pytest.mark.parametrize("name,params_b", [
    ("qwen2.5-7b-int8", 7.6e9), ("qwen2.5-1.5b-bf16", 1.54e9)])
def test_configurations_keep_published_widths_and_depth(name, params_b):
    cfg = load_config(name)
    assert cfg["reduced"] == [] and cfg["source"].startswith("https://huggingface.co/Qwen/")
    assert cfg["num_hidden_layers"] == 28 and cfg["assumed"]
    mf = model_fields(cfg)
    assert mf["head_dim"] == 128 and mf["attn_qkv_bias"] is True
    from dynamo_tpu.engine import ModelConfig

    model = ModelConfig(**mf)
    assert model.param_bytes() / 2 == pytest.approx(params_b, rel=0.02)
    entry = next(c for c in manifest.load()["configs"] if c["name"] == name)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
