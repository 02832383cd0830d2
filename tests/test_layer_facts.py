"""What other modules must know of a layer type sits on its `LayerOp`
(`ops.layers.register(flops=, time_sharding=)`): every type with blobs of
its own that is not one of Caffe's answers both, so that the next type
cannot be counted as zero FLOPs or cut over `sp` by omission.  Beside it,
what the deleted copies of the benchmark's references protected (a
reference imports nothing from the program), and the six language
builders against the net texts the benchmark runs."""

import ast
import json
import os

import pytest

from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.proto import NetParameter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Caffe's own parametrised types: weight x positions is their count
# (`utils.flops`), and none has a time axis it could refuse to cut.
# Frozen: a type registered after PR 45 is not one of them.
CAFFE = {"BatchNorm", "Bias", "Convolution", "Deconvolution", "Embed",
         "InnerProduct", "LSTM", "PReLU", "Parameter", "RNN", "Scale"}

LANGUAGE = {"kanana2": "kanana2_30b_a3b", "lfm2": "lfm2_24b_a2b",
            "qwen3_next": "qwen3_next_80b_a3b",
            "smallthinker": "smallthinker_21b_a3b",
            "phi4flash": "phi4flash_mini",
            "nemotron_h": "nemotron3_nano_30b_a3b"}


@pytest.mark.parametrize("type_name", [
    t for t in L.supported_types()
    if L.get_op(t).param_specs is not L._no_params and t not in CAFFE])
def test_a_type_with_blobs_answers_flops_and_time_sharding(type_name):
    op = L.get_op(type_name)
    assert callable(op.flops), "register(flops=...): see docs/llm_layers.md"
    assert callable(op.time_sharding), \
        "register(time_sharding=...): see docs/llm_layers.md"


def test_the_caffe_list_is_of_registered_types_with_blobs():
    for t in CAFFE:
        assert L.get_op(t).param_specs is not L._no_params, t


@pytest.mark.parametrize("config", sorted(LANGUAGE.values()))
def test_a_benchmark_reference_imports_nothing_from_the_program(config):
    """The benchmark's plain references are what the system is held to,
    in `tests/` as on the chip: independent of the code under test."""
    path = os.path.join(ROOT, "perfbench", "reference", config + ".py")
    tree = ast.parse(open(path).read())
    modules = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
               for a in n.names]
    modules += [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)]
    assert modules and not [m for m in modules
                            if m.split(".")[0] == "caffeonspark_tpu"]


@pytest.mark.parametrize("builder", sorted(LANGUAGE))
def test_a_builder_writes_the_net_the_benchmark_runs(builder):
    """`perfbench/configs/<config>.net.prototxt` is the builder's net
    without its data layer (the benchmark's window writes that)."""
    config = os.path.join(ROOT, "perfbench", "configs", LANGUAGE[builder])
    want = NetParameter.from_text(open(config + ".net.prototxt").read())
    # what a configuration assumes where its source is silent, it states
    assumed = json.load(open(config + ".json")).get("assumed", {})
    got = getattr(zoo, builder)(**{k: assumed[k] for k in ("embed_std",)
                                   if k in assumed})
    assert got.layer[0].type == "CoSData"
    assert [lp.to_text() for lp in got.layer[1:]] == [
        lp.to_text() for lp in want.layer]
    assert got.name == want.name
