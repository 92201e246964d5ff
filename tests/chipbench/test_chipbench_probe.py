"""The probe of ``correct`` is the configuration's to size and the
architecture's to score (PR 41).

Sizes: ``run.probe_of`` reads them from the configuration file's optional
``probe`` object over ``run.PROBE``; a file without one is probed as before
(same ids from the seed, 2 x 17 x 5 comparisons). The blind spot that made
it necessary, on the CPU at window 8 and blocks of 4: a reference that
leaves the window out passes under a probe that stays inside the window and
fails under one sized as the Laguna cell's is.

Scoring: an architecture module MAY bring ``score_probe``; ``check`` calls
it in place of its own, and ``run_probe`` carries what the program said of
each token beside ``top`` through as ``extra``. A toy architecture that
unmasks blocks (``data/toy_block_arch.py``) is scored from each token's own
row that way, and a wrong ``extra`` fails the same ``compare``.
"""

import copy
import functools
import importlib.util
import json
import random
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import architectures, manifest, run
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.procs import HarnessFault
from chipbench.reference import check

ROOT = Path(__file__).resolve().parents[2]
LAGUNA = "laguna-s-2.1-ep8-9l-bf16"


# -- sizes ---------------------------------------------------------------------------------


def test_a_file_without_the_key_is_probed_as_before():
    # the five that were there before a configuration could size its probe; a
    # later file may bring the key or not
    plain = ("qwen2.5-7b-int8", "qwen2.5-1.5b-bf16", "ouro-2.6b-bf16", "a.x-k1-ep16-bf16",
             "lfm2-24b-a2b-10l-bf16")
    assert set(plain) <= {c["name"] for c in manifest.load()["configs"]}
    for name in plain:
        assert "probe" not in load_config(name)
        assert run.probe_of(load_config(name)) == {"prompt_tokens": 96, "max_tokens": 17, "top": 5}
    assert run.PROBE == {"prompt_tokens": 96, "max_tokens": 17, "top": 5}


def test_the_laguna_file_sizes_its_probe_past_the_window():
    cfg = load_config(LAGUNA)
    probe = run.probe_of(cfg)
    assert probe == {"prompt_tokens": 640, "max_tokens": 17, "top": 5}
    block = cfg["serve"]["engine"]["block_size"]
    # a window and four blocks before the first generated token, so four blocks
    # have been released and the table shifted (the issue's fall-back: 1,056 + 33
    # read over the tolerance on one seed of 42 on the v5e, PERF.md section 6)
    assert probe["prompt_tokens"] == cfg["sliding_window"] + 4 * block
    assert probe["max_tokens"] == run.PROBE["max_tokens"]
    # a configuration's file may hold further keys: the driver compares the
    # catalog's numbers at the top level, and ``probe`` is a group of its own
    entry = next(c for c in manifest.load()["configs"] if c["name"] == LAGUNA)
    assert "probe" not in entry["reduced"]


@pytest.mark.parametrize("own", [{"top": 9}, {"prompt_tokens": 0}, {"max_tokens": 2.5},
                                 {"prompt_tokens": 64, "tokens": 3}])
def test_a_probe_the_harness_cannot_read_is_a_harness_fault(own):
    with pytest.raises(HarnessFault, match="probe"):
        run.probe_of({"name": "x", "probe": own})


def fake_cluster():
    return SimpleNamespace(workers=[SimpleNamespace(name="w0")], children=None)


@pytest.mark.parametrize("own,tokens", [(None, (96, 17)), ({"prompt_tokens": 20}, (20, 17)),
                                        ({"prompt_tokens": 640, "max_tokens": 33}, (640, 33))])
def test_the_sizes_reach_the_worker_from_the_files_key_and_nowhere_else(monkeypatch, own, tokens):
    sent = {}

    def side_call(worker, kind, body, children, timeout):
        sent.update(kind=kind, body=body)
        served = {"tokens": [1], "top_ids": [[1]], "top_lps": [[-0.5]], "cached_tokens": 0}
        return {"served": [served, served], "megastep_k": 8, "scored": {"sequences": [
            {"top_lps": [[-0.5]], "argmax": [1], "argmax_lp": [-0.5], "finite": True}] * 2}}

    monkeypatch.setattr(run, "side_call", side_call)
    config = {"name": "x", "vocab_size": 384, **({"probe": own} if own else {})}
    seed = 3000000019
    verdict = run.reference_check(fake_cluster(), config, seed)
    assert verdict["ok"] and verdict["compared"] == 2
    body = sent["body"]
    assert sent["kind"] == "ref" and set(body) == {"prompt_ids", "prompt_tokens", "max_tokens",
                                                   "top"}
    assert (len(body["prompt_ids"]), body["max_tokens"], body["top"]) == (*tokens, 5)
    # the ids are the seed's, drawn as they always were: a longer probe goes on
    # where the shorter one stops
    rng = random.Random(seed ^ 0x5EED)
    assert body["prompt_ids"] == [rng.randrange(1, 384) for _ in range(tokens[0])]


# -- the blind spot, and its cure ------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_laguna_core():
    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-laguna-rehearsal")
    assert cfg["sliding_window"] == 8 and cfg["serve"]["engine"]["block_size"] == 4
    return cfg, EngineCore(ModelConfig(**model_fields(cfg)),
                           EngineConfig(**engine_overrides(cfg)), seed=5)


def checked_through_the_harness(monkeypatch, cfg, core, own, reference_faults=()):
    """``run.reference_check`` as a run makes it, the worker's side answered
    in this process by the function the worker calls; the reference with
    ``reference_faults`` left out of it."""
    module = architectures.of(cfg)
    if reference_faults:
        monkeypatch.setattr(module, "reference_logits", functools.partial(
            module.reference_logits, faults=tuple(reference_faults)))
    sizes = []

    def side_call(worker, kind, body, children, timeout):
        sizes.append((len(body["prompt_ids"]), body["max_tokens"]))
        return json.loads(json.dumps(check.score_request(core, cfg, body)))

    monkeypatch.setattr(run, "side_call", side_call)
    verdict = run.reference_check(fake_cluster(), {**cfg, "probe": own}, 3000000019)
    return verdict, sizes[0]


# 4 + 3 tokens are to a window of 8 what the default 96 + 17 are to one of 512;
# 24 + 3 = a window and four blocks, then part of a block, as 640 + 17 are.
INSIDE = {"prompt_tokens": 4, "max_tokens": 3}
AS_THE_CELL = {"prompt_tokens": 24, "max_tokens": 3}


@pytest.mark.parametrize("own", [INSIDE, AS_THE_CELL])
def test_the_sound_reference_passes_under_either_probe(monkeypatch, tiny_laguna_core, own):
    cfg, core = tiny_laguna_core
    verdict, sizes = checked_through_the_harness(monkeypatch, cfg, core, own)
    assert sizes == (own["prompt_tokens"], own["max_tokens"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-3
    assert verdict["compared"] == 2 * own["max_tokens"] * 5


def test_a_reference_without_the_window_passes_inside_it_and_fails_past_it(
        monkeypatch, tiny_laguna_core):
    cfg, core = tiny_laguna_core
    blind, _ = checked_through_the_harness(monkeypatch, cfg, core, INSIDE, ["window"])
    assert blind["ok"] and blind["max_abs_diff"] < 1e-3      # the blind spot: nothing to see
    seen, _ = checked_through_the_harness(monkeypatch, cfg, core, AS_THE_CELL, ["window"])
    assert not seen["ok"] and seen["max_abs_diff"] > 2 * check.LOGPROB_ATOL


@pytest.mark.parametrize("name", ["fp8", "window", "gate"])
def test_the_control_comes_out_as_not_correct(tiny_laguna_core, name):
    """``reference/control.py`` at a size a test run can hold: through
    ``run.reference_check`` and ``compare`` at the probe sized as the cell's,
    the sound comparison passes, and the reference at the precision below
    bfloat16 in the program's place (``fp8``) or with a mechanism left out
    fails by a multiple of the tolerance. On the v5e at 640 + 17 (and at 1,056 + 33): PERF.md."""
    from chipbench.reference import control

    cfg, core = tiny_laguna_core
    got = control.verdicts(core, {**cfg, "probe": AS_THE_CELL}, 3000000019, [name])
    assert got["sound"]["ok"] and got["sound"]["compared"] == 2 * 3 * 5
    assert not got[name]["ok"] and got[name]["compared"] == 2 * 3 * 5
    assert got[name]["max_abs_diff"] > 2 * check.LOGPROB_ATOL
    assert run.side_call.__module__ == "chipbench.run"      # put back


def test_the_lower_precision_stands_in_the_programs_place(tiny_laguna_core):
    from chipbench.reference import control

    cfg, core = tiny_laguna_core
    prompt = [3, 7, 1, 12, 5, 9, 2, 8, 4, 6, 11, 10]
    probe = check.run_probe(core, prompt, 5, 5, "low")
    low = control.lowered(cfg, core.params, prompt, probe, 5, "fp8")
    # shaped as a served probe, read along the ENGINE's tokens; its own first
    # choices are what compare holds against the reference's
    assert low["sequence"] == probe["tokens"] and len(low["tokens"]) == 5
    assert [ids[0] for ids in low["top_ids"]] == low["tokens"]
    assert all(lps == sorted(lps, reverse=True) and len(lps) == 5 for lps in low["top_lps"])
    assert low["top_lps"] != probe["top_lps"]


# -- scoring by the architecture ---------------------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    spec = importlib.util.spec_from_file_location(
        "toy_block_arch", ROOT / "tests" / "chipbench" / "data" / "toy_block_arch.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TOY_BODY = {"prompt_ids": [3, 7, 1, 12, 5, 9], "max_tokens": 10, "top": 5}


def test_run_probe_carries_every_other_key_of_an_entry_as_extra(toy):
    # asked for, and only then: the six architectures here get a probe without it
    assert "extra" not in check.run_probe(toy.Core(11), TOY_BODY["prompt_ids"], 10, 5, "toy")
    probe = check.run_probe(toy.Core(11), TOY_BODY["prompt_ids"], 10, 5, "toy", extra=True)
    assert len(probe["tokens"]) == 10 and len(probe["extra"]) == 10
    assert all(set(e) == {"token_id", "logprob", "block", "step"} for e in probe["extra"])
    assert [e["block"] for e in probe["extra"]] == [0] * 4 + [1] * 4 + [2] * 2   # the last one cut
    # confidence-ordered, not left to right: some block was unmasked out of order
    steps = [e["step"] for e in probe["extra"]]
    assert sorted(steps[:4]) == [0, 1, 2, 3] and steps != sorted(steps[:4]) * 2 + [0, 1]


def test_an_engine_entry_without_other_keys_gives_empty_extra():
    seq = SimpleNamespace(finish=None, num_cached_tokens=0)

    class Core:
        def add_request(self, request):
            return seq

        def step(self):
            seq.finish = "length"
            return [(seq, SimpleNamespace(token_ids=[4], logprobs=[{"top": [[4, -0.1]]}]))]

    assert check.run_probe(Core(), [1, 2], 1, 1, "bare", extra=True)["extra"] == [{}]


def test_check_scores_with_the_architectures_own_score_probe(monkeypatch, toy):
    monkeypatch.setattr(architectures, "of", lambda cfg: toy)
    cfg = {"name": "toy", "model_type": "toy_block_arch"}
    got = check.score_request(toy.Core(11), cfg, TOY_BODY)
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-5 and verdict["compared"] == 2 * 10 * 5
    assert verdict["argmax_mismatches"] == 0
    # the default scorer reads generated token j from row len(prompt) + j - 1 of
    # ONE causal forward: it cannot describe this program, and says so
    served = got["served"][0]
    default = check.score_probe(cfg, toy.make_params(11), TOY_BODY["prompt_ids"], served)
    assert not check.compare([served], {"sequences": [default]})["ok"]


def test_a_wrong_extra_fails_the_same_compare(monkeypatch, toy):
    monkeypatch.setattr(architectures, "of", lambda cfg: toy)
    cfg = {"name": "toy", "model_type": "toy_block_arch"}
    got = check.score_request(toy.Core(11), cfg, TOY_BODY)
    served = copy.deepcopy(got["served"][0])
    for j, e in enumerate(served["extra"]):
        size = min(4, 10 - j // 4 * 4)     # the last block is cut to two
        e["step"] = size - 1 - e["step"]   # the steps told backwards: legal, and not what ran
    wrong = toy.score_probe(cfg, toy.make_params(11), TOY_BODY["prompt_ids"], served)
    verdict = check.compare([served], {"sequences": [wrong]})
    assert not verdict["ok"] and verdict["max_abs_diff"] > check.LOGPROB_ATOL


@pytest.mark.parametrize("claim", ["told as run", "told as the reference would"])
def test_a_legal_but_wrong_order_is_caught_where_a_replay_passes(monkeypatch, toy, claim):
    """``extra`` is the program's claim, not the reference's input: a program
    that unmasks left to right fails whether it tells the truth (a replay of
    its steps agrees with it token by token; the reference is surest of other
    places) or claims the order the reference would have kept (its tokens
    were not read from those inputs)."""
    monkeypatch.setattr(architectures, "of", lambda cfg: toy)
    cfg = {"name": "toy", "model_type": "toy_block_arch"}
    got = check.score_request(toy.Core(11, order="left"), cfg, TOY_BODY)
    served = got["served"][0]
    assert [e["step"] for e in served["extra"]] == [0, 1, 2, 3, 0, 1, 2, 3, 0, 1]
    if claim == "told as run":
        verdict = check.compare(got["served"], got["scored"])
        # token by token the replay agrees: only the order gives it away
        assert verdict["max_abs_diff"] < 1e-5 and verdict["argmax_mismatches"] > 0
    else:
        sound = check.run_probe(toy.Core(11), TOY_BODY["prompt_ids"], 10, 5, "toy", extra=True)
        served = dict(served, extra=sound["extra"])
        scored = toy.score_probe(cfg, toy.make_params(11), TOY_BODY["prompt_ids"], served)
        verdict = check.compare([served], {"sequences": [scored]})
    assert not verdict["ok"]


@pytest.mark.parametrize("steps", [[0, 0, 1, 2, 0, 1, 2, 3, 0, 1], [1, 2, 3, 4, 0, 1, 2, 3, 0, 1],
                                   [0, 1, 2, 3, 0, 1, 2, 3, 0]])
def test_an_illegal_schedule_is_not_scored(toy, steps):
    probe = check.run_probe(toy.Core(11), TOY_BODY["prompt_ids"], 10, 5, "toy", extra=True)
    probe["extra"] = [dict(e, step=s) for e, s in zip(probe["extra"], steps)]
    scored = toy.score_probe({"name": "toy", "model_type": "toy_block_arch"}, toy.make_params(11),
                             TOY_BODY["prompt_ids"], probe)
    assert scored["finite"] is False
    assert not check.compare([probe], {"sequences": [scored]})["ok"]


def test_a_module_without_one_gets_the_default(monkeypatch):
    for name in architectures.known():
        module = architectures.get(name)
        assert not any(hasattr(module, member) for member in architectures.OPTIONAL), name
    calls = []
    probe = {"tokens": [1], "top_ids": [[1]], "top_lps": [[-0.5]]}
    monkeypatch.setattr(check, "run_probe", lambda *a, extra: dict(probe) if not extra else None)
    monkeypatch.setattr(check, "score_probe", lambda cfg, params, prompt, p: calls.append(p) or {})
    core = SimpleNamespace(params=None, engine=SimpleNamespace(megastep=8))
    check.score_request(core, load_config("tiny-rehearsal"), {"prompt_ids": [1], "max_tokens": 1,
                                                               "top": 1})
    assert len(calls) == 1      # the default, once: the repeat asked the same
