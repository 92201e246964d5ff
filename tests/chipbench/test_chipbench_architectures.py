"""An architecture is a module found by ``model_type``: the lookup, the
key map, the counts it hands the yardstick, and that nothing else in the
benchmark knows an architecture."""

import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import architectures, peaks
from chipbench.architectures import qwen2
from chipbench.configs import load_config, model_fields
from chipbench.readers import trace_reduce

CHIPBENCH = Path(architectures.__file__).resolve().parents[1]
QWEN_CONFIGS = ["qwen2.5-7b-int8", "qwen2.5-1.5b-bf16"]


def test_found_by_model_type():
    assert "qwen2" in architectures.known()
    assert architectures.get("qwen2") is qwen2
    assert architectures.of({"model_type": "qwen2"}) is qwen2
    assert all(hasattr(qwen2, name) for name in architectures.SURFACE)


@pytest.mark.parametrize("cfg", [{"model_type": "gpt5"}, {"model_type": "../peaks"},
                                 {"name": "no-type"}])
def test_unknown_model_type_lists_the_known(cfg):
    with pytest.raises(architectures.UnknownArchitecture) as err:
        architectures.of(cfg)
    assert all(name in str(err.value) for name in architectures.known())


def test_a_module_short_of_the_surface_is_refused(tmp_path, monkeypatch):
    (tmp_path / "half.py").write_text("KEYS = {}\n")
    monkeypatch.setattr(architectures, "__path__", [*architectures.__path__, str(tmp_path)])
    with pytest.raises(architectures.UnknownArchitecture, match="reference_logits"):
        architectures.get("half")


# model_fields of the two configurations, as the parent of PR 26 (one key
# map for every file, in configs.py) gave them.
PINNED = {
    "qwen2.5-7b-int8": dict(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944, num_layers=28,
        num_heads=28, num_kv_heads=4, head_dim=128, rope_theta=1000000.0,
        rms_norm_eps=1e-06, tie_embeddings=False, attn_qkv_bias=True, dtype="bfloat16",
        name="qwen2.5-7b-int8"),
    "qwen2.5-1.5b-bf16": dict(
        vocab_size=151936, hidden_size=1536, intermediate_size=8960, num_layers=28,
        num_heads=12, num_kv_heads=2, head_dim=128, rope_theta=1000000.0,
        rms_norm_eps=1e-06, tie_embeddings=True, attn_qkv_bias=True, dtype="bfloat16",
        name="qwen2.5-1.5b-bf16"),
}


@pytest.mark.parametrize("name", QWEN_CONFIGS)
def test_model_fields_are_field_for_field_the_parents(name):
    assert model_fields(load_config(name)) == PINNED[name]


def test_a_stated_head_dim_wins_over_the_derived_one():
    cfg = {**load_config("qwen2.5-1.5b-bf16"), "head_dim": 64}
    assert model_fields(cfg)["head_dim"] == 64


@pytest.mark.parametrize("name", QWEN_CONFIGS)
@pytest.mark.parametrize("count,call", [
    ("kv_bytes_per_token", lambda f, mf: (f(mf), f(mf, 1))),
    ("attn_decode_bytes_per_layer", lambda f, mf: (f([33, 700, 1], mf, 32), f([9], mf, 8, 1))),
    ("forward_flops_per_token", lambda f, mf: (f(mf), f(mf, 1000))),
])
def test_qwen2_counts_are_the_dense_functions_of_peaks(name, count, call):
    mf = model_fields(load_config(name))
    assert call(getattr(qwen2, count), mf) == call(getattr(peaks, count), mf)


@pytest.mark.parametrize("name", QWEN_CONFIGS)
@pytest.mark.parametrize("quant", [None, "int8"])
def test_a_dense_weight_stream_takes_no_notice_of_the_traffic(name, quant):
    mf = model_fields(load_config(name))
    want = peaks.decode_weight_bytes(mf, quant)
    assert qwen2.decode_weight_bytes(mf, quant) == want
    assert qwen2.decode_weight_bytes(
        mf, quant, architectures.Observed(decode_lanes_mean=3.5)) == want


OPEN = """dynamo_engine_decode_live_lanes_total{service="engine"} 100.0
dynamo_scheduler_megastep_dispatches_total{service="engine"} 10.0
dynamo_engine_expert_tokens_total{expert="3",service="engine"} 7.0
"""
CLOSE = """dynamo_engine_decode_live_lanes_total{service="engine"} 1060.0
dynamo_scheduler_megastep_dispatches_total{service="engine"} 50.0
dynamo_engine_expert_tokens_total{expert="3",service="engine"} 19.0
"""


def test_the_reader_hands_over_what_the_counters_saw_of_the_window():
    seen = trace_reduce._observed(SimpleNamespace(
        scrape_open={"worker": [OPEN]}, scrape_close={"worker": [CLOSE]}))
    assert seen.decode_lanes_mean == pytest.approx(960 / 40)
    assert seen.counter("dynamo_engine_expert_tokens_total", {"expert": "3"}) == 12.0
    assert seen.counter("dynamo_no_such_total") is None
    nothing = trace_reduce._observed(SimpleNamespace(scrape_open={}, scrape_close={}))
    assert nothing.decode_lanes_mean is None
    assert architectures.UNKNOWN.decode_lanes_mean is None
    assert architectures.UNKNOWN.counter("anything") is None


def _names_of_architectures() -> re.Pattern:
    """Published keys that are not also ``ModelConfig`` fields, the leaves
    of the engine's parameter tree, and every ``model_type`` (but not a
    configuration's name such as qwen2.5-7b-int8)."""
    words = {"wqkv", "bqkv", "wgu", "w_down"}
    for name in architectures.known():
        words |= {theirs for theirs, ours in architectures.get(name).KEYS.items()
                  if theirs != ours}
    types = "|".join(map(re.escape, architectures.known()))
    return re.compile(rf"\b(?:{'|'.join(sorted(words))})\b|\b(?:{types})\b(?!\.\d)")


def test_nothing_outside_architectures_names_one():
    pattern = _names_of_architectures()
    assert pattern.search('lp["wgu"]') and pattern.search("import qwen2")
    assert pattern.search("qwen2.forward(") and pattern.search('cfg["num_hidden_layers"]')
    assert not pattern.search("rehearse_v5e qwen2.5-1.5b-bf16 hidden_size lm_head")
    own = {CHIPBENCH / "reference" / f"{name}.py" for name in architectures.known()}
    found = []
    for path in sorted(CHIPBENCH.rglob("*.py")):
        if "architectures" in path.parts or path in own:
            continue   # the modules themselves, and the plain reference of each
        found += [f"{path.relative_to(CHIPBENCH)}:{n}: {line.strip()}"
                  for n, line in enumerate(path.read_text().splitlines(), 1)
                  if pattern.search(line)]
    assert found == []
