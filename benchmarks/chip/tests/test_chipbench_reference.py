"""An LLM configuration describes itself: its file names the reference
module that judges it (``spec.load_reference``), states the program's
settings (``ARCH_KEYS``, then ``"program_keys"``), and its reference
gives the FLOP count ``mfu.llm`` reads. A second architecture then runs
through the harness on new files alone: ``tests/tiny-standin-llama.json``
on the program's ``deepseek-7b`` entry, judged by
``tests/ref_standin_llama.py``."""
import os
import re

import jax
import pytest

import _chipbench
import drive_llm
import spec
from test_chipbench_llm import TINY, _run

STANDIN = _chipbench.load(os.path.join(_chipbench.TESTS,
                                       "tiny-standin-llama.json"))
QWEN3_TINY = _chipbench.load(os.path.join(_chipbench.TESTS,
                                          "tiny-qwen3-1.7b-cut.json"))


def _cell(config: dict):
    cell = _chipbench.tiny_cell("qwen3-g22", **TINY)
    cell.config = config
    return cell


def test_a_second_architecture_runs_on_new_files_alone():
    """Set-up, window and check of ``bench.run`` on the stand-in
    configuration: the program's ``deepseek-7b`` entry sized by the file,
    judged by the stand-in's own reference module, and correct."""
    r = _run(_cell(STANDIN))
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["checks"]) == {"delta1_gap", "delta3_gap"}


def test_the_configurations_reference_judges():
    """The check's reference side is the module the configuration names:
    the same program run, judged by ``ref_qwen3`` and by the stand-in,
    reads the leaves and numbers of each module."""
    d = drive_llm.LLMCell(_cell(dict(QWEN3_TINY)), _chipbench.BIG_SEED,
                          jax.devices())
    d.setup()
    d.release()
    own = d.reference_norms()
    d.c = dict(d.c, reference="tests/ref_standin_llama")
    other = d.reference_norms()
    assert "layers.0.attn.q_norm" in own[1]
    assert "layers.0.attn.q_norm" not in other[1]
    assert d.check(own) != d.check(other)


@pytest.mark.parametrize("reference", [None, "no_such_reference",
                                       "../chip/ref_qwen3", "ref_qwen3.py"])
def test_missing_or_unknown_reference_fails_at_setup(reference):
    config = dict(QWEN3_TINY)
    del config["reference"]
    if reference is not None:
        config["reference"] = reference
    d = drive_llm.LLMCell(_cell(config), _chipbench.BIG_SEED, jax.devices())
    with pytest.raises((ValueError, FileNotFoundError),
                       match=re.escape(repr(reference))):
        d.setup()
    assert d.rounds_done == 0


@pytest.mark.parametrize("arch, keys, field, value", [
    ("olmoe-1b-7b", {"n_routed_experts": "moe.n_experts"},
     ("moe", "n_experts"), 8),
    ("olmoe-1b-7b", {"num_experts_per_tok": "moe.top_k"},
     ("moe", "top_k"), 6),
    ("olmoe-1b-7b", {"moe_intermediate_size": "d_ff"}, ("d_ff",), 1408),
    ("qwen3-1.7b", {"qk_norm": "qk_norm"}, ("qk_norm",), False),
])
def test_program_keys_reach_the_program(arch, keys, field, value):
    c = {"program_arch": arch, "hidden_size": 64, "n_routed_experts": 8,
         "num_experts_per_tok": 6, "moe_intermediate_size": 1408,
         "qk_norm": False, "program_keys": keys}
    cfg = drive_llm.arch_config(c)
    assert cfg.d_model == 64          # ARCH_KEYS still apply
    got = cfg
    for name in field:
        got = getattr(got, name)
    assert got == value


@pytest.mark.parametrize("arch, target", [
    ("olmoe-1b-7b", "moe.no_such_field"),
    ("olmoe-1b-7b", "no_such_field"),
    ("olmoe-1b-7b", "d_model.size"),
    ("qwen3-1.7b", "moe.n_experts"),   # a dense entry has no moe
])
def test_program_keys_naming_no_field_raise(arch, target):
    c = {"program_arch": arch, "n_routed_experts": 8,
         "program_keys": {"n_routed_experts": target}}
    with pytest.raises(KeyError, match=re.escape(target)):
        drive_llm.arch_config(c)


@pytest.mark.parametrize("config", [STANDIN, QWEN3_TINY],
                         ids=lambda c: c["name"])
def test_mfu_reads_the_configurations_count(config):
    """``mfu.llm`` takes the FLOPs per token from the reference module
    the configuration names."""
    read = spec.load_reader("mfu.llm")
    ctx = {"rates": {"train_tokens_per_s": 1000.0}, "config": config,
           "traffic": {"seq_len": 32}, "peaks": {"bf16_flops_per_s": 1e14}}
    per_token = spec.load_reference(
        config["reference"]).train_flops_per_token(config, 32)
    assert read(ctx) == pytest.approx(100.0 * per_token * 1000.0 / 1e14,
                                      rel=1e-12)
    with pytest.raises(FileNotFoundError, match="no_such_reference"):
        read(dict(ctx, config=dict(config, reference="no_such_reference")))
