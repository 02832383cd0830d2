"""Model zoo: programmatic NetParameters for the reference's benchmark
workloads (BASELINE.md: LeNet-MNIST, CIFAR-10 quick, CaffeNet-ImageNet).
Authored here so the framework works stand-alone; the unmodified
reference prototxts in /root/reference/data parse identically."""

from __future__ import annotations

import math

from ..proto import NetParameter, parse_net_prototxt

LENET = """
name: "LeNet"
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 64 channels: 1 height: 28 width: 28 }
  transform_param { scale: 0.00390625 } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 20 kernel_size: 5 stride: 1
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "conv2" type: "Convolution" bottom: "pool1" top: "conv2"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param { num_output: 50 kernel_size: 5 stride: 1
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layer { name: "pool2" type: "Pooling" bottom: "conv2" top: "pool2"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "ip1" type: "InnerProduct" bottom: "pool2" top: "ip1"
  param { lr_mult: 1 } param { lr_mult: 2 }
  inner_product_param { num_output: 500
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  param { lr_mult: 1 } param { lr_mult: 2 }
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layer { name: "accuracy" type: "Accuracy" bottom: "ip2" bottom: "label"
  top: "accuracy" include { phase: TEST } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2" bottom: "label"
  top: "loss" }
"""

_CONV = """
layer {{ name: "{name}" type: "Convolution" bottom: "{bottom}" top: "{name}"
  param {{ lr_mult: 1 decay_mult: 1 }} param {{ lr_mult: 2 decay_mult: 0 }}
  convolution_param {{ num_output: {n} kernel_size: {k} {extra}
    weight_filler {{ type: "gaussian" std: {std} }}
    bias_filler {{ type: "constant" value: {bias} }} }} }}
layer {{ name: "relu_{name}" type: "ReLU" bottom: "{name}" top: "{name}" }}
"""

_FC = """
layer {{ name: "{name}" type: "InnerProduct" bottom: "{bottom}" top: "{name}"
  param {{ lr_mult: 1 decay_mult: 1 }} param {{ lr_mult: 2 decay_mult: 0 }}
  inner_product_param {{ num_output: {n}
    weight_filler {{ type: "gaussian" std: {std} }}
    bias_filler {{ type: "constant" value: {bias} }} }} }}
"""


def caffenet(batch_size: int = 64, num_classes: int = 1000,
             crop: int = 227) -> NetParameter:
    """AlexNet-style CaffeNet (the bvlc_reference_net workload)."""
    t = f"""
name: "CaffeNet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param {{ batch_size: {batch_size} channels: 3
    height: {crop} width: {crop} }} }}
"""
    t += _CONV.format(name="conv1", bottom="data", n=96, k=11,
                      extra="stride: 4", std=0.01, bias=0)
    t += """
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
layer { name: "norm1" type: "LRN" bottom: "pool1" top: "norm1"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
"""
    t += _CONV.format(name="conv2", bottom="norm1", n=256, k=5,
                      extra="pad: 2 group: 2", std=0.01, bias=1)
    t += """
layer { name: "pool2" type: "Pooling" bottom: "conv2" top: "pool2"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
layer { name: "norm2" type: "LRN" bottom: "pool2" top: "norm2"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
"""
    t += _CONV.format(name="conv3", bottom="norm2", n=384, k=3,
                      extra="pad: 1", std=0.01, bias=0)
    t += _CONV.format(name="conv4", bottom="conv3", n=384, k=3,
                      extra="pad: 1 group: 2", std=0.01, bias=1)
    t += _CONV.format(name="conv5", bottom="conv4", n=256, k=3,
                      extra="pad: 1 group: 2", std=0.01, bias=1)
    t += """
layer { name: "pool5" type: "Pooling" bottom: "conv5" top: "pool5"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    t += _FC.format(name="fc6", bottom="pool5", n=4096, std=0.005, bias=1)
    t += """
layer { name: "relu6" type: "ReLU" bottom: "fc6" top: "fc6" }
layer { name: "drop6" type: "Dropout" bottom: "fc6" top: "fc6"
  dropout_param { dropout_ratio: 0.5 } }
"""
    t += _FC.format(name="fc7", bottom="fc6", n=4096, std=0.005, bias=1)
    t += """
layer { name: "relu7" type: "ReLU" bottom: "fc7" top: "fc7" }
layer { name: "drop7" type: "Dropout" bottom: "fc7" top: "fc7"
  dropout_param { dropout_ratio: 0.5 } }
"""
    t += _FC.format(name="fc8", bottom="fc7", n=num_classes, std=0.01,
                    bias=0)
    t += """
layer { name: "accuracy" type: "Accuracy" bottom: "fc8" bottom: "label"
  top: "accuracy" include { phase: TEST } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc8" bottom: "label"
  top: "loss" }
"""
    return parse_net_prototxt(t)


def alexnet(batch_size: int = 64, num_classes: int = 1000,
            crop: int = 227) -> NetParameter:
    """Original bvlc_alexnet (Krizhevsky 2012 order: **norm before
    pool**, unlike bvlc_reference_net/CaffeNet which pools first).
    Same parameter shapes as caffenet(); the relu→norm adjacency makes
    this the zoo family where the COS_FUSE_RELU_LRN peephole fires
    (norm1/norm2) — and the 55×55/27×27 pre-pool LRN extents make it
    the LRN-heaviest workload in the zoo."""
    t = f"""
name: "AlexNet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param {{ batch_size: {batch_size} channels: 3
    height: {crop} width: {crop} }} }}
"""
    t += _CONV.format(name="conv1", bottom="data", n=96, k=11,
                      extra="stride: 4", std=0.01, bias=0)
    t += """
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool1" type: "Pooling" bottom: "norm1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    t += _CONV.format(name="conv2", bottom="pool1", n=256, k=5,
                      extra="pad: 2 group: 2", std=0.01, bias=1)
    t += """
layer { name: "norm2" type: "LRN" bottom: "conv2" top: "norm2"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool2" type: "Pooling" bottom: "norm2" top: "pool2"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    t += _CONV.format(name="conv3", bottom="pool2", n=384, k=3,
                      extra="pad: 1", std=0.01, bias=0)
    t += _CONV.format(name="conv4", bottom="conv3", n=384, k=3,
                      extra="pad: 1 group: 2", std=0.01, bias=1)
    t += _CONV.format(name="conv5", bottom="conv4", n=256, k=3,
                      extra="pad: 1 group: 2", std=0.01, bias=1)
    t += """
layer { name: "pool5" type: "Pooling" bottom: "conv5" top: "pool5"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    t += _FC.format(name="fc6", bottom="pool5", n=4096, std=0.005, bias=1)
    t += """
layer { name: "relu6" type: "ReLU" bottom: "fc6" top: "fc6" }
layer { name: "drop6" type: "Dropout" bottom: "fc6" top: "fc6"
  dropout_param { dropout_ratio: 0.5 } }
"""
    t += _FC.format(name="fc7", bottom="fc6", n=4096, std=0.005, bias=1)
    t += """
layer { name: "relu7" type: "ReLU" bottom: "fc7" top: "fc7" }
layer { name: "drop7" type: "Dropout" bottom: "fc7" top: "fc7"
  dropout_param { dropout_ratio: 0.5 } }
"""
    t += _FC.format(name="fc8", bottom="fc7", n=num_classes, std=0.01,
                    bias=0)
    t += """
layer { name: "accuracy" type: "Accuracy" bottom: "fc8" bottom: "label"
  top: "accuracy" include { phase: TEST } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc8" bottom: "label"
  top: "loss" }
"""
    return parse_net_prototxt(t)


def lenet(batch_size: int = 64) -> NetParameter:
    npm = parse_net_prototxt(LENET)
    for lyr in npm.layer:
        if lyr.type == "MemoryData":
            lyr.memory_data_param.batch_size = batch_size
    return npm


def vgg16(batch_size: int = 32, num_classes: int = 1000,
          image_size: int = 224) -> NetParameter:
    """VGG-16 (Simonyan & Zisserman): 13 conv3x3 + 3 fc."""
    t = f"""
name: "VGG16"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param {{ batch_size: {batch_size} channels: 3
    height: {image_size} width: {image_size} }} }}
"""
    cfg = [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]
    bottom = "data"
    for block, (n, reps) in enumerate(cfg, 1):
        for r in range(1, reps + 1):
            name = f"conv{block}_{r}"
            t += _CONV.format(name=name, bottom=bottom, n=n, k=3,
                              extra="pad: 1", std=0.01, bias=0)
            bottom = name
        t += f"""
layer {{ name: "pool{block}" type: "Pooling" bottom: "{bottom}"
  top: "pool{block}" pooling_param {{ pool: MAX kernel_size: 2
  stride: 2 }} }}
"""
        bottom = f"pool{block}"
    for i, n in ((6, 4096), (7, 4096)):
        t += _FC.format(name=f"fc{i}", bottom=bottom, n=n, std=0.005,
                        bias=1)
        t += f"""
layer {{ name: "relu{i}" type: "ReLU" bottom: "fc{i}" top: "fc{i}" }}
layer {{ name: "drop{i}" type: "Dropout" bottom: "fc{i}" top: "fc{i}"
  dropout_param {{ dropout_ratio: 0.5 }} }}
"""
        bottom = f"fc{i}"
    t += _FC.format(name="fc8", bottom=bottom, n=num_classes, std=0.01,
                    bias=0)
    t += """
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc8"
  bottom: "label" top: "loss" }
layer { name: "accuracy" type: "Accuracy" bottom: "fc8" bottom: "label"
  top: "accuracy" include { phase: TEST } }
"""
    return parse_net_prototxt(t)


_CONV_BN = """
layer {{ name: "{name}" type: "Convolution" bottom: "{bottom}" top: "{name}"
  param {{ lr_mult: 1 decay_mult: 1 }}
  convolution_param {{ num_output: {n} kernel_size: {k} {extra}
    bias_term: false weight_filler {{ type: "msra" }} }} }}
layer {{ name: "bn_{name}" type: "BatchNorm" bottom: "{name}" top: "{name}" }}
layer {{ name: "scale_{name}" type: "Scale" bottom: "{name}" top: "{name}"
  scale_param {{ bias_term: true }} }}
"""


def _res_block(t: str, name: str, bottom: str, mid: int, out: int,
               stride: int, project: bool) -> str:
    """ResNet bottleneck: 1x1(mid) → 3x3(mid) → 1x1(out) + identity/
    projection shortcut, Eltwise SUM, ReLU."""
    t += _CONV_BN.format(name=f"{name}_branch2a", bottom=bottom, n=mid,
                         k=1, extra=f"stride: {stride}")
    t += (f'\nlayer {{ name: "{name}_branch2a_relu" type: "ReLU" '
          f'bottom: "{name}_branch2a" top: "{name}_branch2a" }}\n')
    t += _CONV_BN.format(name=f"{name}_branch2b",
                         bottom=f"{name}_branch2a", n=mid, k=3,
                         extra="pad: 1")
    t += (f'\nlayer {{ name: "{name}_branch2b_relu" type: "ReLU" '
          f'bottom: "{name}_branch2b" top: "{name}_branch2b" }}\n')
    t += _CONV_BN.format(name=f"{name}_branch2c",
                         bottom=f"{name}_branch2b", n=out, k=1, extra="")
    if project:
        t += _CONV_BN.format(name=f"{name}_branch1", bottom=bottom,
                             n=out, k=1, extra=f"stride: {stride}")
        shortcut = f"{name}_branch1"
    else:
        shortcut = bottom
    t += f"""
layer {{ name: "{name}" type: "Eltwise" bottom: "{shortcut}"
  bottom: "{name}_branch2c" top: "{name}" }}
layer {{ name: "{name}_relu" type: "ReLU" bottom: "{name}"
  top: "{name}" }}
"""
    return t


def resnet50(batch_size: int = 32, num_classes: int = 1000
             ) -> NetParameter:
    """ResNet-50 (He et al.): bottleneck residual stacks with
    BatchNorm+Scale, Eltwise shortcuts — the post-AlexNet ImageNet
    workhorse, exercising BN/Scale/Eltwise at scale."""
    t = f"""
name: "ResNet50"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param {{ batch_size: {batch_size} channels: 3
    height: 224 width: 224 }} }}
"""
    t += _CONV_BN.format(name="conv1", bottom="data", n=64, k=7,
                         extra="pad: 3 stride: 2")
    t += """
layer { name: "conv1_relu" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    cfg = [("res2", 64, 256, 3, 1), ("res3", 128, 512, 4, 2),
           ("res4", 256, 1024, 6, 2), ("res5", 512, 2048, 3, 2)]
    bottom = "pool1"
    for stage, mid, out, blocks, stride in cfg:
        for b in range(blocks):
            name = f"{stage}{chr(ord('a') + b)}"
            t = _res_block(t, name, bottom, mid, out,
                           stride if b == 0 else 1, project=(b == 0))
            bottom = name
    t += f"""
layer {{ name: "pool5" type: "Pooling" bottom: "{bottom}" top: "pool5"
  pooling_param {{ pool: AVE global_pooling: true }} }}
layer {{ name: "fc1000" type: "InnerProduct" bottom: "pool5"
  top: "fc1000"
  param {{ lr_mult: 1 decay_mult: 1 }} param {{ lr_mult: 2 decay_mult: 0 }}
  inner_product_param {{ num_output: {num_classes}
    weight_filler {{ type: "xavier" }}
    bias_filler {{ type: "constant" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "fc1000"
  bottom: "label" top: "loss" }}
layer {{ name: "accuracy" type: "Accuracy" bottom: "fc1000"
  bottom: "label" top: "accuracy" include {{ phase: TEST }} }}
"""
    return parse_net_prototxt(t)


def transformer_lm(vocab: int = 1000, d_model: int = 128, heads: int = 4,
                   layers: int = 2, seq: int = 32, batch: int = 8
                   ) -> NetParameter:
    """Small causal transformer language model (extension family: the
    reference tops out at LSTM; this exercises MultiHeadAttention from a
    plain prototxt).  Time-major (T, B) int inputs like the LSTM path."""
    t = f"""
name: "TransformerLM"
layer {{ name: "data" type: "CoSData" top: "input_sentence"
  top: "target_sentence"
  cos_data_param {{ batch_size: {batch}
    top {{ name: "input_sentence" type: INT_ARRAY channels: {seq}
          sample_num_axes: 1 transpose: true }}
    top {{ name: "target_sentence" type: INT_ARRAY channels: {seq}
          sample_num_axes: 1 transpose: true }} }} }}
layer {{ name: "embed" type: "Embed" bottom: "input_sentence"
  top: "h0" embed_param {{ input_dim: {vocab} num_output: {d_model}
    bias_term: false
    weight_filler {{ type: "uniform" min: -0.05 max: 0.05 }} }} }}
"""
    bottom = "h0"
    hd = d_model // heads
    for i in range(1, layers + 1):
        t += f"""
layer {{ name: "attn{i}" type: "MultiHeadAttention" bottom: "{bottom}"
  top: "attn{i}"
  attention_param {{ num_heads: {heads} head_dim: {hd} causal: true }} }}
layer {{ name: "res{i}a" type: "Eltwise" bottom: "{bottom}"
  bottom: "attn{i}" top: "res{i}a" }}
layer {{ name: "ff{i}" type: "InnerProduct" bottom: "res{i}a"
  top: "ff{i}" inner_product_param {{ num_output: {4 * d_model} axis: 2
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "ff{i}_relu" type: "ReLU" bottom: "ff{i}" top: "ff{i}" }}
layer {{ name: "ff{i}_out" type: "InnerProduct" bottom: "ff{i}"
  top: "ff{i}_out" inner_product_param {{ num_output: {d_model} axis: 2
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "res{i}b" type: "Eltwise" bottom: "res{i}a"
  bottom: "ff{i}_out" top: "res{i}b" }}
"""
        bottom = f"res{i}b"
    t += f"""
layer {{ name: "logits" type: "InnerProduct" bottom: "{bottom}"
  top: "logits" inner_product_param {{ num_output: {vocab} axis: 2
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
  bottom: "target_sentence" top: "loss"
  loss_param {{ ignore_label: -1 }} softmax_param {{ axis: 2 }} }}
"""
    return parse_net_prototxt(t)


# ---------------------------------------------------------------------------
# the language builders' shared text: inputs and embedding, one pre-norm
# residual block, the dense gated feed-forward, norm + head + loss
# ---------------------------------------------------------------------------

def _gauss(std) -> str:
    return f'weight_filler {{ type: "gaussian" std: {std} }}'


def _lm_inputs(name, batch, seq, vocab, hidden, filler, extra="") -> str:
    """The net's name, its time-major (T, B) int tops `input_ids` /
    `target_ids` and the embedding of the ids into `h0`."""
    return f"""
name: "{name}"
layer {{ name: "data" type: "CoSData" top: "input_ids" top: "target_ids"
  cos_data_param {{ batch_size: {batch}
    top {{ name: "input_ids" type: INT_ARRAY channels: {seq}
          sample_num_axes: 1 transpose: true }}
    top {{ name: "target_ids" type: INT_ARRAY channels: {seq}
          sample_num_axes: 1 transpose: true }} }} }}
layer {{ name: "embed" type: "Embed" bottom: "input_ids" top: "h0"
  {extra} embed_param {{ input_dim: {vocab} num_output: {hidden}
    bias_term: false {filler} }} }}
"""


def _norm(kind, name, bottom, top, tag, eps) -> str:
    param = {"RMSNorm": "rms_norm_param", "LayerNorm": "layer_norm_param"}
    return f"""
layer {{ name: "{name}" type: "{kind}" bottom: "{bottom}" top: "{top}"
  {tag} {param[kind]} {{ eps: {eps} }} }}"""


def _ip(name, bottom, top, n, tag, filler, extra="") -> str:
    return f"""
layer {{ name: "{name}" type: "InnerProduct" bottom: "{bottom}" top: "{top}"
  {tag} {extra} inner_product_param {{ num_output: {n} axis: 2
    bias_term: false {filler} }} }}"""


def _gated_ffn(p, width, hidden, tag, filler) -> str:
    """Block `p`'s dense SiLU-gated feed-forward, "{p}.n2" -> "{p}.f"."""
    return (_ip(f"{p}.gate", f"{p}.n2", f"{p}.g", width, tag, filler)
            + _ip(f"{p}.up", f"{p}.n2", f"{p}.u", width, tag, filler) + f"""
layer {{ name: "{p}.act" type: "SiLU" bottom: "{p}.g" top: "{p}.g" {tag} }}
layer {{ name: "{p}.prod" type: "Eltwise" bottom: "{p}.g" bottom: "{p}.u"
  top: "{p}.gu" {tag} eltwise_param {{ operation: PROD }} }}"""
            + _ip(f"{p}.down", f"{p}.gu", f"{p}.f", hidden, tag, filler))


def _block(p, h, mixer, ffn, tag, eps, *, ffn_tag=None, norm="RMSNorm"):
    """One pre-norm residual block named `p` over the blob `h`:

        h1 = h + mixer(norm1(h)),    out = h1 + ffn(norm2(h1))

    `mixer` is the text of the layers that read "{p}.n1" and write
    "{p}.a", `ffn` of those that read "{p}.n2" and write "{p}.f"; `tag`
    is the `recompute_block` field that the block's own layers carry
    ("" for none), `ffn_tag` that of the second half where it is a
    block of its own.  A block of ONE operator has one half: `ffn` None
    leaves  out = h + mixer(norm1(h)),  `mixer` None  out = h +
    ffn(norm2(h)).  -> (text, the blob that leaves the block)."""
    ffn_tag = tag if ffn_tag is None else ffn_tag
    h1 = f"{p}.h1" if mixer and ffn else f"{p}.out" if mixer else h
    first = "" if not mixer else (
        _norm(norm, f"{p}.norm1", h, f"{p}.n1", tag, eps) + mixer + f"""
layer {{ name: "{p}.res1" type: "Eltwise" bottom: "{h}" bottom: "{p}.a"
  top: "{h1}" {tag} }}""")
    second = "" if not ffn else (
        _norm(norm, f"{p}.norm2", h1, f"{p}.n2", ffn_tag, eps) + ffn + f"""
layer {{ name: "{p}.res2" type: "Eltwise" bottom: "{h1}" bottom: "{p}.f"
  top: "{p}.out" {ffn_tag} }}""")
    return first + second + "\n", f"{p}.out"


def _lm_head(h, vocab, filler, eps, *, norm="RMSNorm", extra="") -> str:
    """The last norm, the logits over `vocab` ids and the loss against
    `target_ids`."""
    return (_norm(norm, "head.norm", h, "head.n", "", eps)
            + _ip("head.logits", "head.n", "logits", vocab, "", filler, extra)
            + """
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
  bottom: "target_ids" top: "loss" softmax_param { axis: 2 } }
""")


def kanana2(vocab: int = 16032, hidden: int = 2048, heads: int = 32,
            qk_nope: int = 128, qk_rope: int = 64, v_head: int = 128,
            kv_lora_rank: int = 512, dense_width: int = 6144,
            expert_width: int = 768, experts: int = 128, top_k: int = 6,
            shared_experts: int = 2, experts_held: int = 16,
            first_expert: int = 0, routed_scaling_factor: float = 2.448,
            expert_layers: int = 5, seq: int = 4096, batch: int = 2,
            rope_theta: float = 1e6, eps: float = 1e-6,
            init_std: float = 0.02, recompute: bool = True
            ) -> NetParameter:
    """kakaocorp/kanana-2-30b-a3b (`model_type: deepseek_v3`) as one
    chip's share of an expert-parallel deployment: pre-norm residual
    blocks of latent attention (no q compression, rotary positions on
    64 of the 192 q/k dims) and a SiLU-gated feed-forward that is dense
    in the leading block and, in the `expert_layers` that follow,
    `experts` sigmoid-routed experts of which this net holds
    `experts_held` from `first_expert` on, plus `shared_experts` shared
    ones as one gated FFN.  The defaults are the published widths with
    the cut of `perfbench/configs/kanana2_30b_a3b.json` (16 of 128
    experts, an eighth of the vocabulary, 1 + 5 of 48 layers);
    `experts_held=experts`, `vocab=128256`, `expert_layers=47` is the
    whole model.  Time-major (T, B) int tops `input_ids` /
    `target_ids`; every block is one `recompute_block`; each expert
    layer's `moe_stats` / `moe_rows` tops are net outputs."""
    gauss = _gauss(init_std)
    t = _lm_inputs("Kanana2", batch, seq, vocab, hidden, gauss)
    h = "h0"
    for i in range(expert_layers + 1):
        p = f"L{i}"
        tag = f'recompute_block: "{p}"' if recompute else ""
        attn = f"""
layer {{ name: "{p}.attn" type: "LatentAttention" bottom: "{p}.n1"
  top: "{p}.a" {tag}
  attention_param {{ num_heads: {heads} causal: true
    qk_nope_head_dim: {qk_nope} qk_rope_head_dim: {qk_rope}
    v_head_dim: {v_head} kv_lora_rank: {kv_lora_rank}
    rope_theta: {rope_theta} rms_norm_eps: {eps} {gauss} }} }}"""
        if i == 0:
            ffn = _gated_ffn(p, dense_width, hidden, tag, gauss)
        else:
            # blobs: router, bias (moves only the choice: frozen),
            # W_gate, W_up, W_down, S_gate, S_up, S_down
            ffn = f"""
layer {{ name: "{p}.moe" type: "MixtureOfExperts" bottom: "{p}.n2"
  top: "{p}.f" top: "{p}.moe_stats" top: "{p}.moe_rows" {tag}
  param {{ lr_mult: 1 }} param {{ lr_mult: 0 decay_mult: 0 }}
  moe_param {{ num_experts: {experts} hidden_dim: {expert_width}
    top_k: {top_k} dispatch: "dropless" scoring: "sigmoid"
    selection_bias: true routed_scaling_factor: {routed_scaling_factor}
    gated: true shared_hidden_dim: {shared_experts * expert_width}
    experts_held: {experts_held} first_expert: {first_expert}
    {gauss} }} }}"""
        block, h = _block(p, h, attn, ffn, tag, eps)
        t += block
    return parse_net_prototxt(t + _lm_head(h, vocab, gauss, eps))


# lfm2_moe's published operator schedule (LFM2-24B-A2B, 40 layers):
# conv, conv, attention, then three convs and an attention repeating,
# a conv last
LFM2_LAYER_TYPES = tuple(
    "full_attention" if i >= 2 and (i - 2) % 4 == 0 else "conv"
    for i in range(40))


def lfm2(vocab: int = 8192, hidden: int = 2048, heads: int = 32,
         kv_heads: int = 8, head_dim: int = 64, dense_width: int = 11776,
         expert_width: int = 1536, experts: int = 64, top_k: int = 4,
         experts_held: int = 8, first_expert: int = 0,
         routed_scaling_factor: float = 1.0, norm_epsilon: float = 1e-6,
         layer_types=LFM2_LAYER_TYPES, num_dense_layers: int = 2,
         first_layer: int = 1, layers: int = 7, conv_taps: int = 3,
         conv_bias: bool = False, seq: int = 8192, batch: int = 1,
         rope_theta: float = 1e6, eps: float = 1e-5,
         init_std: float = 0.02, recompute: bool = True) -> NetParameter:
    """LiquidAI/LFM2-24B-A2B (`model_type: lfm2_moe`) as one chip's
    share of an expert-parallel deployment: pre-norm residual blocks
    whose operator follows `layer_types` — the gated short convolution
    (`conv`) or grouped-query attention with q/k norms and rotary
    positions (`full_attention`) — and whose feed-forward is a dense
    SiLU-gated one in the published layers below `num_dense_layers` and
    `experts` sigmoid-routed experts (no shared one) after, of which
    this net holds `experts_held` from `first_expert` on.  The net is
    the published layers [`first_layer`, `first_layer` + `layers`),
    named L0, L1, ... in the order they run.  The defaults are the
    published widths with the cut of `perfbench/configs/
    lfm2_24b_a2b.json` (8 of 64 experts, an eighth of the vocabulary,
    published layers 1-7: one dense conv layer, then attention, three
    convs, attention, conv over experts); `experts_held=64,
    vocab=65536, first_layer=0, layers=40` is the whole model.
    Time-major (T, B) int tops `input_ids` / `target_ids` (one row of
    8,192 by default); every block is one `recompute_block`; each
    expert layer's `moe_stats` / `moe_rows` tops are net outputs."""
    gauss = _gauss(init_std)
    if first_layer + layers > len(layer_types):
        raise ValueError(f"lfm2: layers [{first_layer}, "
                         f"{first_layer + layers}) of {len(layer_types)}")
    t = _lm_inputs("LFM2", batch, seq, vocab, hidden, gauss)
    h = "h0"
    for i in range(layers):
        p = f"L{i}"
        published = first_layer + i
        tag = f'recompute_block: "{p}"' if recompute else ""
        kind = layer_types[published]
        if kind == "conv":
            mixer = f"""
layer {{ name: "{p}.conv" type: "ShortConv" bottom: "{p}.n1" top: "{p}.a"
  {tag} short_conv_param {{ taps: {conv_taps}
    bias_term: {"true" if conv_bias else "false"} {gauss} }} }}"""
        elif kind == "full_attention":
            mixer = f"""
layer {{ name: "{p}.attn" type: "GroupedQueryAttention" bottom: "{p}.n1"
  top: "{p}.a" {tag}
  attention_param {{ num_heads: {heads} num_kv_heads: {kv_heads}
    head_dim: {head_dim} causal: true qk_norm: true rotary: true
    rope_theta: {rope_theta} rms_norm_eps: {eps} {gauss} }} }}"""
        else:
            raise ValueError(f"lfm2: layer type {kind!r}")
        if published < num_dense_layers:
            ffn = _gated_ffn(p, dense_width, hidden, tag, gauss)
        else:
            # blobs: router, bias (moves only the choice: frozen),
            # W_gate, W_up, W_down
            ffn = f"""
layer {{ name: "{p}.moe" type: "MixtureOfExperts" bottom: "{p}.n2"
  top: "{p}.f" top: "{p}.moe_stats" top: "{p}.moe_rows" {tag}
  param {{ lr_mult: 1 }} param {{ lr_mult: 0 decay_mult: 0 }}
  moe_param {{ num_experts: {experts} hidden_dim: {expert_width}
    top_k: {top_k} dispatch: "dropless" scoring: "sigmoid"
    selection_bias: true routed_scaling_factor: {routed_scaling_factor}
    norm_epsilon: {norm_epsilon} gated: true
    experts_held: {experts_held} first_expert: {first_expert}
    {gauss} }} }}"""
        block, h = _block(p, h, mixer, ffn, tag, eps)
        t += block
    return parse_net_prototxt(t + _lm_head(h, vocab, gauss, eps))


# smallthinker's published layouts (SmallThinker-21BA3B-Instruct, 52
# layers): layer 0 of every four attends to its whole past and carries
# no position, the other three turn q and k by position and see a window
SMALLTHINKER_LAYOUT = tuple(int(i % 4 != 0) for i in range(52))


def smallthinker(vocab: int = 18992, hidden: int = 2560, heads: int = 28,
                 kv_heads: int = 4, head_dim: int = 128,
                 expert_width: int = 768, experts: int = 64,
                 top_k: int = 6, experts_held: int = 8,
                 first_expert: int = 0, window: int = 4096,
                 sliding_window_layout=SMALLTHINKER_LAYOUT,
                 rope_layout=SMALLTHINKER_LAYOUT, first_layer: int = 0,
                 layers: int = 4, seq: int = 16384, batch: int = 1,
                 rope_theta: float = 1.5e6, eps: float = 1e-6,
                 init_std: float = 0.02, embed_std: float | None = None,
                 router_reads: str = "n1",
                 recompute: bool = True) -> NetParameter:
    """PowerInfer/SmallThinker-21BA3B-Instruct (`model_name:
    smallthinker_21b_instruct`) as one chip's share of an
    expert-parallel deployment: pre-norm residual blocks of
    grouped-query attention (no q / k norm, no bias) over `experts`
    ReLU-gated experts of which `top_k` a token (softmax over the
    chosen logits, none shared), this net holding `experts_held` from
    `first_expert` on.  Published layer i sees a window of `window`
    keys where `sliding_window_layout[i]` is 1 and its whole past where
    it is 0, and turns q and k by position where `rope_layout[i]` is 1
    (0: the layer carries no position at all).  The router reads the
    block's normed INPUT (`router_reads: "n1"`, the second bottom of
    the expert layer), the experts the normed residual after the
    attention; `"n2"` is a router that reads what the experts read.
    The net is the published layers [`first_layer`, `first_layer` +
    `layers`), named L0, L1, ...  The defaults are the published widths
    with the cut of `perfbench/configs/smallthinker_21b_a3b.json` (8 of
    64 experts, an eighth of the vocabulary, published layers 0-3: one
    global layer, three window layers); `experts_held=64, vocab=151936,
    layers=52` is the whole model.  Time-major (T, B) int tops
    `input_ids` / `target_ids` (one row of 16,384 by default); every
    block is one `recompute_block`; each expert layer's `moe_stats` /
    `moe_rows` tops are net outputs.  Every matrix is filled gaussian
    `init_std`, the embedding gaussian `embed_std` where one is given."""
    gauss = _gauss(init_std)
    if first_layer + layers > min(len(sliding_window_layout),
                                  len(rope_layout)):
        raise ValueError(f"smallthinker: layers [{first_layer}, "
                         f"{first_layer + layers}) of "
                         f"{len(sliding_window_layout)}")
    if router_reads not in ("n1", "n2"):
        raise ValueError(f"smallthinker: router_reads {router_reads!r}")
    t = _lm_inputs("SmallThinker", batch, seq, vocab, hidden,
                   gauss if embed_std is None else _gauss(embed_std))
    h = "h0"
    for i in range(layers):
        p = f"L{i}"
        published = first_layer + i
        tag = f'recompute_block: "{p}"' if recompute else ""
        win = (f" window: {window}" if sliding_window_layout[published]
               else "")
        rot = "true" if rope_layout[published] else "false"
        router = (f' bottom: "{p}.n1"' if router_reads == "n1" else "")
        attn = f"""
layer {{ name: "{p}.attn" type: "GroupedQueryAttention" bottom: "{p}.n1"
  top: "{p}.a" {tag}
  attention_param {{ num_heads: {heads} num_kv_heads: {kv_heads}
    head_dim: {head_dim} causal: true rotary: {rot}
    rope_theta: {rope_theta}{win} {gauss} }} }}"""
        moe = f"""
layer {{ name: "{p}.moe" type: "MixtureOfExperts" bottom: "{p}.n2"{router}
  top: "{p}.f" top: "{p}.moe_stats" top: "{p}.moe_rows" {tag}
  moe_param {{ num_experts: {experts} hidden_dim: {expert_width}
    top_k: {top_k} dispatch: "dropless" scoring: "softmax" gated: true
    gate_activation: "relu"
    experts_held: {experts_held} first_expert: {first_expert}
    {gauss} }} }}"""
        block, h = _block(p, h, attn, moe, tag, eps)
        t += block
    return parse_net_prototxt(t + _lm_head(h, vocab, gauss, eps))


def phi4flash_kinds(total_layers: int = 32):
    """Published layer i's kind in a SambaY model of `total_layers`
    (arXiv:2507.06607; Phi-4-mini-flash-reasoning: 32): the self-decoder
    alternates Mamba (even) and sliding-window attention (odd) up to
    layer N/2 - 1; layer N/2 is the Mamba whose scan output is the
    memory, layer N/2 + 1 the full attention whose keys and values the
    cross-decoder reads; from N/2 + 2 on, Gated Memory Units (even) and
    cross-attention (odd)."""
    half = total_layers // 2
    return tuple(
        ("mamba" if i < half else "mamba_memory" if i == half else "gmu")
        if i % 2 == 0 else
        ("window" if i < half else "full_kv" if i == half + 1 else "cross")
        for i in range(total_layers))


def phi4flash(vocab: int = 25008, hidden: int = 2560, heads: int = 40,
              kv_heads: int = 20, head_dim: int = 64,
              intermediate: int = 10240, d_inner: int = 5120,
              d_state: int = 16, d_conv: int = 4, dt_rank: int = 160,
              window: int = 512, total_layers: int = 32,
              first_layer: int = 14, layers: int = 6, seq: int = 8192,
              batch: int = 1, eps: float = 1e-5, init_std: float = 0.02,
              lambda_std: float = 0.1, conv_bound: float = 0.5,
              chunk: int = 64, tie: bool = True, recompute: bool = True,
              blocks_a_layer: int = 2) -> NetParameter:
    """microsoft/Phi-4-mini-flash-reasoning (`model_type: phi4flash`,
    the SambaY decoder-hybrid-decoder of arXiv:2507.06607) as a pipeline
    stage: pre-norm residual layers, `x + Mixer_i(LN(x))` then
    `x + MLP(LN(x))` with a plain LayerNorm (scale and bias) and a dense
    SiLU-gated feed-forward, whose mixer follows the published index i
    (`phi4flash_kinds`): a Mamba selective scan, differential attention
    under a window of `window` keys, the Mamba that also hands on its
    scan output as the memory, the differential attention over the
    whole past that also hands on its keys and values, a Gated Memory
    Unit that gates the memory, or a cross-attention layer that has
    `W_q` and `W_o` alone and reads those keys and values.  No layer
    carries a position.  lambda_init of a differential layer is
    0.8 - 0.6 exp(-0.3 i) by its PUBLISHED index.  The embedding and
    the head are one blob (`param { name: "E" }`) unless `tie` is off.
    The net is the published layers [`first_layer`, `first_layer` +
    `layers`), named L0, L1, ...; a cut that holds a GMU or a cross
    layer holds the layer that makes what it reads.  The defaults are
    the published widths with the cut of `perfbench/configs/
    phi4flash_mini.json` (published layers 14-19: every kind once, Mamba
    and attention twice; an eighth of the vocabulary); `layers=32,
    vocab=200064, first_layer=0` is the whole model.  Time-major (T, B)
    int tops `input_ids` / `target_ids` (one row of 8,192 by default);
    a layer's mixer half and its feed-forward half are a
    `recompute_block` each (`blocks_a_layer` 1: one for both).  Every
    matrix is filled gaussian `init_std`, the lambda vectors gaussian
    `lambda_std`, the taps and their bias uniform +-`conv_bound`."""
    kinds = phi4flash_kinds(total_layers)
    if first_layer + layers > total_layers:
        raise ValueError(f"phi4flash: layers [{first_layer}, "
                         f"{first_layer + layers}) of {total_layers}")
    run = kinds[first_layer:first_layer + layers]
    if ("gmu" in run and "mamba_memory" not in run) or \
            ("cross" in run and "full_kv" not in run):
        raise ValueError(
            f"phi4flash: layers [{first_layer}, {first_layer + layers}) "
            "read a memory or keys and values that no layer of the cut "
            "makes")
    gauss = _gauss(init_std)
    shared = 'param { name: "E" }' if tie else ""
    t = _lm_inputs("Phi4Flash", batch, seq, vocab, hidden, gauss, shared)
    h = "h0"
    memory = kv = None
    for i, kind in enumerate(run):
        p = f"L{i}"
        published = first_layer + i
        tag = mlp_tag = ""
        if recompute:
            tag = f'recompute_block: "{p}{".mix" * (blocks_a_layer > 1)}"'
            mlp_tag = (f'recompute_block: "{p}.mlp"' if blocks_a_layer > 1
                       else tag)
        lam = 0.8 - 0.6 * math.exp(-0.3 * published)
        attn = f"""num_heads: {heads} num_kv_heads: {kv_heads}
    head_dim: {head_dim} causal: true differential: true
    lambda_init: {lam:.8f} rms_norm_eps: {eps}
    lambda_filler {{ type: "gaussian" std: {lambda_std} }} {gauss}"""
        if kind in ("mamba", "mamba_memory"):
            tops = f'top: "{p}.a"'
            if kind == "mamba_memory":
                memory = f"{p}.memory"
                tops += f' top: "{memory}"'
            mixer = f"""
layer {{ name: "{p}.mamba" type: "Mamba" bottom: "{p}.n1" {tops} {tag}
  mamba_param {{ d_inner: {d_inner} d_state: {d_state} d_conv: {d_conv}
    dt_rank: {dt_rank} chunk: {chunk} {gauss}
    conv_filler {{ type: "uniform" min: {-conv_bound} max: {conv_bound} }}
  }} }}"""
        elif kind in ("window", "full_kv"):
            tops, more = f'top: "{p}.a"', ""
            if kind == "window":
                more = f" window: {window}"
            else:
                kv = (f"{p}.k", f"{p}.v")
                tops += f' top: "{kv[0]}" top: "{kv[1]}"'
                more = " emit_kv: true"
            mixer = f"""
layer {{ name: "{p}.attn" type: "GroupedQueryAttention" bottom: "{p}.n1"
  {tops} {tag}
  attention_param {{ {attn}{more} }} }}"""
        elif kind == "gmu":
            mixer = f"""
layer {{ name: "{p}.gmu" type: "GatedMemoryUnit" bottom: "{p}.n1"
  bottom: "{memory}" top: "{p}.a" {tag}
  gated_memory_unit_param {{ {gauss} }} }}"""
        else:
            mixer = f"""
layer {{ name: "{p}.attn" type: "GroupedQueryAttention" bottom: "{p}.n1"
  bottom: "{kv[0]}" bottom: "{kv[1]}" top: "{p}.a" {tag}
  attention_param {{ {attn} shared_kv: true }} }}"""
        block, h = _block(
            p, h, mixer, _gated_ffn(p, intermediate, hidden, mlp_tag, gauss),
            tag, eps, ffn_tag=mlp_tag, norm="LayerNorm")
        t += block
    return parse_net_prototxt(t + _lm_head(
        h, vocab, gauss, eps, norm="LayerNorm", extra=shared))


# nemotron_h's published operator schedule (NVIDIA-Nemotron-3-Nano-30B-
# A3B, 52 blocks of ONE operator each): M a Mamba-2 mixer, E an expert
# layer, * an attention layer
NEMOTRON_H_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_h(vocab: int = 16384, hidden: int = 2688, heads: int = 32,
               kv_heads: int = 2, head_dim: int = 128,
               mamba_heads: int = 64, mamba_head_dim: int = 64,
               n_groups: int = 8, d_state: int = 128, d_conv: int = 4,
               chunk: int = 128, expert_width: int = 1856,
               shared_width: int = 3712, experts: int = 128,
               top_k: int = 6, experts_held: int = 8,
               first_expert: int = 0, routed_scaling_factor: float = 2.5,
               norm_epsilon: float = 1e-20,
               pattern: str = NEMOTRON_H_PATTERN, first_layer: int = 34,
               layers: int = 9, seq: int = 8192, batch: int = 1,
               eps: float = 1e-5, init_std: float = 0.02,
               conv_bound: float = 0.5, recompute: bool = True
               ) -> NetParameter:
    """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type: nemotron_h`)
    as one chip's share of an expert-parallel pipeline stage: pre-norm
    residual blocks of ONE operator each, `x + Op(RMSNorm(x))`, Op by
    the published `pattern` letter: `M` the Mamba-2 mixer
    (`mamba_heads` heads of `mamba_head_dim`, `n_groups` groups of
    `d_state` states, `d_conv` taps with a bias, chunks of `chunk`,
    a gated grouped RMSNorm), `E` `experts` sigmoid-routed ungated
    squared-ReLU experts (the `top_k` largest scores plus a frozen
    selection bias, renormalised over the chosen plus `norm_epsilon`,
    times `routed_scaling_factor`) of which this net holds
    `experts_held` from `first_expert` on, plus one ungated shared
    expert of `shared_width`, `*` grouped-query attention without bias
    and without positions.  The net is the published blocks
    [`first_layer`, `first_layer` + `layers`), named L0, L1, ... in
    the order they run.  The defaults are the published widths with
    the cut of `perfbench/configs/nemotron3_nano_30b_a3b.json` (8 of
    128 experts, an eighth of the vocabulary, published blocks 34-42,
    `EMEMEMEM*`: the one whole run of nine between two attention
    layers); `experts_held=128, vocab=131072, first_layer=0,
    layers=52` is the whole model.  Time-major (T, B) int tops
    `input_ids` / `target_ids` (one row of 8,192 by default); every
    block is one `recompute_block`; each expert layer's `moe_stats` /
    `moe_rows` tops are net outputs.  Every matrix is filled gaussian
    `init_std`, the taps and their bias uniform +-`conv_bound`; the
    head is untied."""
    gauss = _gauss(init_std)
    if first_layer + layers > len(pattern):
        raise ValueError(f"nemotron_h: blocks [{first_layer}, "
                         f"{first_layer + layers}) of {len(pattern)}")
    t = _lm_inputs("NemotronH", batch, seq, vocab, hidden, gauss)
    h = "h0"
    for i, kind in enumerate(pattern[first_layer:first_layer + layers]):
        p = f"L{i}"
        tag = f'recompute_block: "{p}"' if recompute else ""
        mixer = ffn = None
        if kind == "M":
            mixer = f"""
layer {{ name: "{p}.mamba2" type: "Mamba2" bottom: "{p}.n1" top: "{p}.a"
  {tag} mamba2_param {{ num_heads: {mamba_heads}
    head_dim: {mamba_head_dim} n_groups: {n_groups} d_state: {d_state}
    d_conv: {d_conv} chunk: {chunk} rms_norm_eps: {eps} {gauss}
    conv_filler {{ type: "uniform" min: {-conv_bound} max: {conv_bound} }}
  }} }}"""
        elif kind == "*":
            mixer = f"""
layer {{ name: "{p}.attn" type: "GroupedQueryAttention" bottom: "{p}.n1"
  top: "{p}.a" {tag}
  attention_param {{ num_heads: {heads} num_kv_heads: {kv_heads}
    head_dim: {head_dim} causal: true rotary: false {gauss} }} }}"""
        elif kind == "E":
            # blobs: router, bias (moves only the choice: frozen), W1,
            # W2, S_up, S_down
            ffn = f"""
layer {{ name: "{p}.moe" type: "MixtureOfExperts" bottom: "{p}.n2"
  top: "{p}.f" top: "{p}.moe_stats" top: "{p}.moe_rows" {tag}
  param {{ lr_mult: 1 }} param {{ lr_mult: 0 decay_mult: 0 }}
  moe_param {{ num_experts: {experts} hidden_dim: {expert_width}
    top_k: {top_k} dispatch: "dropless" scoring: "sigmoid"
    selection_bias: true routed_scaling_factor: {routed_scaling_factor}
    norm_epsilon: {norm_epsilon} gated: false activation: "relu2"
    shared_hidden_dim: {shared_width}
    experts_held: {experts_held} first_expert: {first_expert}
    {gauss} }} }}"""
        else:
            raise ValueError(f"nemotron_h: pattern letter {kind!r}")
        block, h = _block(p, h, mixer, ffn, tag, eps)
        t += block
    return parse_net_prototxt(t + _lm_head(h, vocab, gauss, eps))


# qwen3_next's published operator schedule (Qwen3-Next-80B-A3B, 48
# layers, full_attention_interval 4): three Gated DeltaNet layers, then
# one gated full-attention layer, repeating
QWEN3_NEXT_LAYER_TYPES = tuple(
    "full_attention" if (i + 1) % 4 == 0 else "linear_attention"
    for i in range(48))


def qwen3_next(vocab: int = 18992, hidden: int = 2048, heads: int = 16,
               kv_heads: int = 2, head_dim: int = 256,
               rotary_dim: int = 64, linear_k_heads: int = 16,
               linear_v_heads: int = 32, linear_k_dim: int = 128,
               linear_v_dim: int = 128, conv_taps: int = 4,
               chunk: int = 64, expert_width: int = 512,
               shared_width: int = 512, experts: int = 512,
               top_k: int = 10, experts_held: int = 32,
               first_expert: int = 0, layer_types=QWEN3_NEXT_LAYER_TYPES,
               first_layer: int = 0, layers: int = 4, seq: int = 8192,
               batch: int = 1, rope_theta: float = 1e7, eps: float = 1e-6,
               init_std: float = 0.02, recompute: bool = True
               ) -> NetParameter:
    """Qwen/Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`) as
    one chip's share of an expert-parallel deployment: pre-norm
    residual blocks whose operator follows `layer_types` — the Gated
    DeltaNet operator (`linear_attention`) or grouped-query attention
    with q/k norms, rotary positions on the first `rotary_dim` dims of
    a head and a sigmoid output gate (`full_attention`) — and whose
    feed-forward is `experts` softmax-routed experts (the `top_k`
    largest, renormalised), of which this net holds `experts_held` from
    `first_expert` on, plus one sigmoid-gated shared expert.  The net is
    the published layers [`first_layer`, `first_layer` + `layers`),
    named L0, L1, ... in the order they run.  The defaults are the
    published widths with the cut of `perfbench/configs/
    qwen3_next_80b_a3b.json` (32 of 512 experts, an eighth of the
    vocabulary, published layers 0-3: one whole period);
    `experts_held=512, vocab=151936, layers=48` is the whole model.
    Every RMSNorm is stored as one scale filled 1 (the family's 1 + w
    with w filled 0).  Time-major (T, B) int tops `input_ids` /
    `target_ids` (one row of 8,192 by default); every block is one
    `recompute_block`; each expert layer's `moe_stats` / `moe_rows`
    tops are net outputs."""
    gauss = _gauss(init_std)
    if first_layer + layers > len(layer_types):
        raise ValueError(f"qwen3_next: layers [{first_layer}, "
                         f"{first_layer + layers}) of {len(layer_types)}")
    t = _lm_inputs("Qwen3Next", batch, seq, vocab, hidden, gauss)
    h = "h0"
    for i in range(layers):
        p = f"L{i}"
        tag = f'recompute_block: "{p}"' if recompute else ""
        kind = layer_types[first_layer + i]
        if kind == "linear_attention":
            mixer = f"""
layer {{ name: "{p}.gdn" type: "GatedDeltaNet" bottom: "{p}.n1" top: "{p}.a"
  {tag} gated_delta_net_param {{ num_k_heads: {linear_k_heads}
    num_v_heads: {linear_v_heads} head_k_dim: {linear_k_dim}
    head_v_dim: {linear_v_dim} conv_taps: {conv_taps} chunk: {chunk}
    rms_norm_eps: {eps} {gauss} }} }}"""
        elif kind == "full_attention":
            mixer = f"""
layer {{ name: "{p}.attn" type: "GroupedQueryAttention" bottom: "{p}.n1"
  top: "{p}.a" {tag}
  attention_param {{ num_heads: {heads} num_kv_heads: {kv_heads}
    head_dim: {head_dim} causal: true qk_norm: true rotary: true
    rotary_dim: {rotary_dim} output_gate: true
    rope_theta: {rope_theta} rms_norm_eps: {eps} {gauss} }} }}"""
        else:
            raise ValueError(f"qwen3_next: layer type {kind!r}")
        # blobs: router, W_gate, W_up, W_down, the shared expert's three
        # and its gate
        moe = f"""
layer {{ name: "{p}.moe" type: "MixtureOfExperts" bottom: "{p}.n2"
  top: "{p}.f" top: "{p}.moe_stats" top: "{p}.moe_rows" {tag}
  moe_param {{ num_experts: {experts} hidden_dim: {expert_width}
    top_k: {top_k} dispatch: "dropless" scoring: "softmax" gated: true
    shared_hidden_dim: {shared_width} shared_gate: true
    experts_held: {experts_held} first_expert: {first_expert}
    {gauss} }} }}"""
        block, h = _block(p, h, mixer, moe, tag, eps)
        t += block
    return parse_net_prototxt(t + _lm_head(h, vocab, gauss, eps))


def lstm_lm(vocab: int = 8801, d_model: int = 1000, seq: int = 20,
            batch_size: int = 32) -> NetParameter:
    """LRCN-shaped recurrent language model: Embed -> cont-gated LSTM
    -> per-step logits (the recurrent half of the reference's COCO
    captioning workload, `lrcn_cos.prototxt`'s 8801-word vocab and
    1000-wide embedding/LSTM; SURVEY §5.7) with the caption tops the
    LRCN pipeline feeds.  The benchmark recurrent family next to the
    CNN zoo (BENCH_MODEL=lstm)."""
    b = batch_size
    t = f"""
name: "LSTMLM"
layer {{ name: "data" type: "CoSData" top: "input_sentence"
  top: "cont_sentence" top: "target_sentence"
  cos_data_param {{ batch_size: {b}
    top {{ name: "input_sentence" type: INT_ARRAY channels: {seq}
          sample_num_axes: 1 transpose: true }}
    top {{ name: "cont_sentence" type: INT_ARRAY channels: {seq}
          sample_num_axes: 1 transpose: true }}
    top {{ name: "target_sentence" type: INT_ARRAY channels: {seq}
          sample_num_axes: 1 transpose: true }} }} }}
layer {{ name: "embedding" type: "Embed" bottom: "input_sentence"
  top: "embedded_input_sentence"
  embed_param {{ input_dim: {vocab} num_output: {d_model}
    bias_term: false
    weight_filler {{ type: "uniform" min: -0.08 max: 0.08 }} }} }}
layer {{ name: "lstm1" type: "LSTM" bottom: "embedded_input_sentence"
  bottom: "cont_sentence" top: "lstm1"
  recurrent_param {{ num_output: {d_model}
    weight_filler {{ type: "uniform" min: -0.08 max: 0.08 }} }} }}
layer {{ name: "predict" type: "InnerProduct" bottom: "lstm1"
  top: "predict" inner_product_param {{ num_output: {vocab} axis: 2
    weight_filler {{ type: "uniform" min: -0.08 max: 0.08 }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "predict"
  bottom: "target_sentence" top: "loss"
  loss_param {{ ignore_label: -1 }} softmax_param {{ axis: 2 }} }}
"""
    return parse_net_prototxt(t)


def _inception(t: str, name: str, bottom: str, c1, c3r, c3, c5r, c5,
               pp) -> str:
    """One GoogLeNet inception module: 1x1 / 3x3 / 5x5 / pool-proj
    branches concatenated on channels."""
    t += _CONV.format(name=f"{name}/1x1", bottom=bottom, n=c1, k=1,
                      extra="", std=0.03, bias=0.2)
    t += _CONV.format(name=f"{name}/3x3_reduce", bottom=bottom, n=c3r,
                      k=1, extra="", std=0.09, bias=0.2)
    t += _CONV.format(name=f"{name}/3x3", bottom=f"{name}/3x3_reduce",
                      n=c3, k=3, extra="pad: 1", std=0.03, bias=0.2)
    t += _CONV.format(name=f"{name}/5x5_reduce", bottom=bottom, n=c5r,
                      k=1, extra="", std=0.2, bias=0.2)
    t += _CONV.format(name=f"{name}/5x5", bottom=f"{name}/5x5_reduce",
                      n=c5, k=5, extra="pad: 2", std=0.03, bias=0.2)
    t += f"""
layer {{ name: "{name}/pool" type: "Pooling" bottom: "{bottom}"
  top: "{name}/pool" pooling_param {{ pool: MAX kernel_size: 3 stride: 1
  pad: 1 }} }}
"""
    t += _CONV.format(name=f"{name}/pool_proj", bottom=f"{name}/pool",
                      n=pp, k=1, extra="", std=0.1, bias=0.2)
    t += f"""
layer {{ name: "{name}/output" type: "Concat"
  bottom: "{name}/1x1" bottom: "{name}/3x3" bottom: "{name}/5x5"
  bottom: "{name}/pool_proj" top: "{name}/output" }}
"""
    return t


def _googlenet_aux_head(idx: int, bottom: str, num_classes: int) -> str:
    """bvlc_googlenet auxiliary classifier (train_val.prototxt loss1/
    loss2 towers): AVE pool 5x5/3 -> 1x1 conv 128 -> fc 1024 ->
    dropout 0.7 -> fc classes, SoftmaxWithLoss weight 0.3, TRAIN only."""
    p = f"loss{idx}"
    return f"""
layer {{ name: "{p}/ave_pool" type: "Pooling" bottom: "{bottom}"
  top: "{p}/ave_pool" include {{ phase: TRAIN }}
  pooling_param {{ pool: AVE kernel_size: 5 stride: 3 }} }}
layer {{ name: "{p}/conv" type: "Convolution" bottom: "{p}/ave_pool"
  top: "{p}/conv" include {{ phase: TRAIN }}
  param {{ lr_mult: 1 decay_mult: 1 }} param {{ lr_mult: 2 decay_mult: 0 }}
  convolution_param {{ num_output: 128 kernel_size: 1
    weight_filler {{ type: "xavier" }}
    bias_filler {{ type: "constant" value: 0.2 }} }} }}
layer {{ name: "{p}/relu_conv" type: "ReLU" bottom: "{p}/conv"
  top: "{p}/conv" include {{ phase: TRAIN }} }}
layer {{ name: "{p}/fc" type: "InnerProduct" bottom: "{p}/conv"
  top: "{p}/fc" include {{ phase: TRAIN }}
  param {{ lr_mult: 1 decay_mult: 1 }} param {{ lr_mult: 2 decay_mult: 0 }}
  inner_product_param {{ num_output: 1024
    weight_filler {{ type: "xavier" }}
    bias_filler {{ type: "constant" value: 0.2 }} }} }}
layer {{ name: "{p}/relu_fc" type: "ReLU" bottom: "{p}/fc"
  top: "{p}/fc" include {{ phase: TRAIN }} }}
layer {{ name: "{p}/drop_fc" type: "Dropout" bottom: "{p}/fc"
  top: "{p}/fc" include {{ phase: TRAIN }}
  dropout_param {{ dropout_ratio: 0.7 }} }}
layer {{ name: "{p}/classifier" type: "InnerProduct" bottom: "{p}/fc"
  top: "{p}/classifier" include {{ phase: TRAIN }}
  param {{ lr_mult: 1 decay_mult: 1 }} param {{ lr_mult: 2 decay_mult: 0 }}
  inner_product_param {{ num_output: {num_classes}
    weight_filler {{ type: "xavier" }}
    bias_filler {{ type: "constant" }} }} }}
layer {{ name: "{p}/loss" type: "SoftmaxWithLoss"
  bottom: "{p}/classifier" bottom: "label" top: "{p}/loss"
  loss_weight: 0.3 include {{ phase: TRAIN }} }}
"""


def googlenet(batch_size: int = 32, num_classes: int = 1000,
              image_size: int = 224, aux_heads: bool = True
              ) -> NetParameter:
    """GoogLeNet / Inception-v1 (bvlc_googlenet topology incl. the two
    TRAIN-phase auxiliary classifier towers, weight 0.3)."""
    t = f"""
name: "GoogLeNet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param {{ batch_size: {batch_size} channels: 3
    height: {image_size} width: {image_size} }} }}
"""
    t += _CONV.format(name="conv1/7x7_s2", bottom="data", n=64, k=7,
                      extra="pad: 3 stride: 2", std=0.01, bias=0.2)
    t += """
layer { name: "pool1_3x3_s2" type: "Pooling" bottom: "conv1/7x7_s2"
  top: "pool1" pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
layer { name: "pool1_norm1" type: "LRN" bottom: "pool1" top: "norm1"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
"""
    t += _CONV.format(name="conv2/3x3_reduce", bottom="norm1", n=64, k=1,
                      extra="", std=0.09, bias=0.2)
    t += _CONV.format(name="conv2/3x3", bottom="conv2/3x3_reduce",
                      n=192, k=3, extra="pad: 1", std=0.03, bias=0.2)
    t += """
layer { name: "conv2_norm2" type: "LRN" bottom: "conv2/3x3" top: "norm2"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool2_3x3_s2" type: "Pooling" bottom: "norm2"
  top: "pool2" pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    t = _inception(t, "inception_3a", "pool2", 64, 96, 128, 16, 32, 32)
    t = _inception(t, "inception_3b", "inception_3a/output",
                   128, 128, 192, 32, 96, 64)
    t += """
layer { name: "pool3_3x3_s2" type: "Pooling"
  bottom: "inception_3b/output" top: "pool3"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    t = _inception(t, "inception_4a", "pool3", 192, 96, 208, 16, 48, 64)
    if aux_heads:
        t += _googlenet_aux_head(1, "inception_4a/output", num_classes)
    t = _inception(t, "inception_4b", "inception_4a/output",
                   160, 112, 224, 24, 64, 64)
    t = _inception(t, "inception_4c", "inception_4b/output",
                   128, 128, 256, 24, 64, 64)
    t = _inception(t, "inception_4d", "inception_4c/output",
                   112, 144, 288, 32, 64, 64)
    if aux_heads:
        t += _googlenet_aux_head(2, "inception_4d/output", num_classes)
    t = _inception(t, "inception_4e", "inception_4d/output",
                   256, 160, 320, 32, 128, 128)
    t += """
layer { name: "pool4_3x3_s2" type: "Pooling"
  bottom: "inception_4e/output" top: "pool4"
  pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""
    t = _inception(t, "inception_5a", "pool4", 256, 160, 320, 32, 128,
                   128)
    t = _inception(t, "inception_5b", "inception_5a/output",
                   384, 192, 384, 48, 128, 128)
    t += f"""
layer {{ name: "pool5_7x7_s1" type: "Pooling"
  bottom: "inception_5b/output" top: "pool5"
  pooling_param {{ pool: AVE global_pooling: true }} }}
layer {{ name: "pool5_drop" type: "Dropout" bottom: "pool5" top: "pool5"
  dropout_param {{ dropout_ratio: 0.4 }} }}
layer {{ name: "loss3/classifier" type: "InnerProduct" bottom: "pool5"
  top: "loss3/classifier"
  param {{ lr_mult: 1 decay_mult: 1 }} param {{ lr_mult: 2 decay_mult: 0 }}
  inner_product_param {{ num_output: {num_classes}
    weight_filler {{ type: "xavier" }}
    bias_filler {{ type: "constant" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "loss3/classifier"
  bottom: "label" top: "loss" }}
layer {{ name: "accuracy" type: "Accuracy" bottom: "loss3/classifier"
  bottom: "label" top: "accuracy" include {{ phase: TEST }} }}
"""
    return parse_net_prototxt(t)
