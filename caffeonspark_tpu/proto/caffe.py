"""Caffe message schema (reconstructed, plus CaffeOnSpark fork extensions).

The reference's schema lives in its absent `caffe-public` submodule
(`caffe.proto`); field numbers here follow upstream BVLC Caffe so that
binary `.caffemodel` / `.binaryproto` / `.solverstate` files and LMDB
`Datum` records interoperate.  CoS fork extensions (`source_class`,
`cos_data_param`, `MemoryDataParameter.{source,dataframe_format,
dataframe_column_select,image_encoded,share_in_parallel}`) have no public
numbers — they are visible only at call sites (SURVEY.md §2.9, e.g.
`DataSource.scala:139`, `ImageDataFrame.scala:35-45`) — so they are
assigned numbers in unclaimed ranges; only their *text*-format names
matter for config compatibility.
"""

from __future__ import annotations

from .descriptor import (BOOL, BYTES, DOUBLE, ENUM, FLOAT, INT32, INT64,
                         MESSAGE, STRING, UINT32, Enum, Field, Message)

# ---------------------------------------------------------------------------
# enums
# ---------------------------------------------------------------------------

Phase = Enum("Phase", TRAIN=0, TEST=1)
PoolMethod = Enum("PoolMethod", MAX=0, AVE=1, STOCHASTIC=2)
NormRegion = Enum("NormRegion", ACROSS_CHANNELS=0, WITHIN_CHANNEL=1)
EltwiseOp = Enum("EltwiseOp", PROD=0, SUM=1, MAX=2)
SnapshotFormat = Enum("SnapshotFormat", HDF5=0, BINARYPROTO=1)
SolverMode = Enum("SolverMode", CPU=0, GPU=1, TPU=2)
SolverType = Enum("SolverType", SGD=0, NESTEROV=1, ADAGRAD=2, RMSPROP=3,
                  ADADELTA=4, ADAM=5)
VarianceNorm = Enum("VarianceNorm", FAN_IN=0, FAN_OUT=1, AVERAGE=2)
DBBackend = Enum("DBBackend", LEVELDB=0, LMDB=1)
NormalizationMode = Enum("NormalizationMode", FULL=0, VALID=1, BATCH_SIZE=2,
                         NONE=3)
# CoS DataFrame top types (DataFrameSource.scala Top class, SURVEY §2.3)
TopBlobType = Enum("TopBlobType", STRING=0, INT=1, FLOAT=2, INT_ARRAY=3,
                   FLOAT_ARRAY=4, RAW_IMAGE=5, ENCODED_IMAGE=6,
                   ENCODED_IMAGE_WITH_DIM=7)


# ---------------------------------------------------------------------------
# basic blobs / data records
# ---------------------------------------------------------------------------

class BlobShape(Message):
    FIELDS = [Field(1, "dim", INT64, repeated=True, packed=True)]


class BlobProto(Message):
    FIELDS = [
        Field(7, "shape", MESSAGE, message=BlobShape),
        Field(5, "data", FLOAT, repeated=True, packed=True),
        Field(6, "diff", FLOAT, repeated=True, packed=True),
        Field(8, "double_data", DOUBLE, repeated=True, packed=True),
        Field(9, "double_diff", DOUBLE, repeated=True, packed=True),
        Field(1, "num", INT32),
        Field(2, "channels", INT32),
        Field(3, "height", INT32),
        Field(4, "width", INT32),
    ]


class BlobProtoVector(Message):
    FIELDS = [Field(1, "blobs", MESSAGE, message=BlobProto, repeated=True)]


class Datum(Message):
    """One LMDB record (image bytes CHW u8 or float_data, + label)."""
    FIELDS = [
        Field(1, "channels", INT32),
        Field(2, "height", INT32),
        Field(3, "width", INT32),
        Field(4, "data", BYTES),
        Field(5, "label", INT32),
        Field(6, "float_data", FLOAT, repeated=True),
        Field(7, "encoded", BOOL, default=False),
    ]


class FillerParameter(Message):
    FIELDS = [
        Field(1, "type", STRING, default="constant"),
        Field(2, "value", FLOAT, default=0.0),
        Field(3, "min", FLOAT, default=0.0),
        Field(4, "max", FLOAT, default=1.0),
        Field(5, "mean", FLOAT, default=0.0),
        Field(6, "std", FLOAT, default=1.0),
        Field(7, "sparse", INT32, default=-1),
        Field(8, "variance_norm", ENUM, enum=VarianceNorm, default=0),
    ]


# ---------------------------------------------------------------------------
# net state / rules / param specs
# ---------------------------------------------------------------------------

class NetState(Message):
    FIELDS = [
        Field(1, "phase", ENUM, enum=Phase, default=Phase.TEST),
        Field(2, "level", INT32, default=0),
        Field(3, "stage", STRING, repeated=True),
    ]


class NetStateRule(Message):
    FIELDS = [
        Field(1, "phase", ENUM, enum=Phase),
        Field(2, "min_level", INT32),
        Field(3, "max_level", INT32),
        Field(4, "stage", STRING, repeated=True),
        Field(5, "not_stage", STRING, repeated=True),
    ]


class ParamSpec(Message):
    FIELDS = [
        Field(1, "name", STRING),
        Field(2, "share_mode", ENUM,
              enum=Enum("DimCheckMode", STRICT=0, PERMISSIVE=1)),
        Field(3, "lr_mult", FLOAT, default=1.0),
        Field(4, "decay_mult", FLOAT, default=1.0),
    ]


# ---------------------------------------------------------------------------
# layer-specific parameter messages
# ---------------------------------------------------------------------------

class TransformationParameter(Message):
    FIELDS = [
        Field(1, "scale", FLOAT, default=1.0),
        Field(2, "mirror", BOOL, default=False),
        Field(3, "crop_size", UINT32, default=0),
        Field(4, "mean_file", STRING),
        Field(5, "mean_value", FLOAT, repeated=True),
        Field(6, "force_color", BOOL, default=False),
        Field(7, "force_gray", BOOL, default=False),
    ]


class LossParameter(Message):
    FIELDS = [
        Field(1, "ignore_label", INT32, default=-1),
        Field(3, "normalization", ENUM, enum=NormalizationMode, default=1),
        Field(2, "normalize", BOOL),
    ]


class AccuracyParameter(Message):
    FIELDS = [
        Field(1, "top_k", UINT32, default=1),
        Field(2, "axis", INT32, default=1),
        Field(3, "ignore_label", INT32, default=-1),
    ]


class ArgMaxParameter(Message):
    FIELDS = [
        Field(1, "out_max_val", BOOL, default=False),
        Field(2, "top_k", UINT32, default=1),
        Field(3, "axis", INT32),
    ]


class ConcatParameter(Message):
    FIELDS = [
        Field(2, "axis", INT32, default=1),
        Field(1, "concat_dim", UINT32, default=1),
    ]


class ConvolutionParameter(Message):
    FIELDS = [
        Field(1, "num_output", UINT32),
        Field(2, "bias_term", BOOL, default=True),
        Field(3, "pad", UINT32, repeated=True),
        Field(4, "kernel_size", UINT32, repeated=True),
        Field(6, "stride", UINT32, repeated=True),
        Field(18, "dilation", UINT32, repeated=True),
        Field(9, "pad_h", UINT32, default=0),
        Field(10, "pad_w", UINT32, default=0),
        Field(11, "kernel_h", UINT32),
        Field(12, "kernel_w", UINT32),
        Field(13, "stride_h", UINT32),
        Field(14, "stride_w", UINT32),
        Field(5, "group", UINT32, default=1),
        Field(7, "weight_filler", MESSAGE, message=FillerParameter),
        Field(8, "bias_filler", MESSAGE, message=FillerParameter),
        Field(15, "engine", ENUM,
              enum=Enum("Engine", DEFAULT=0, CAFFE=1, CUDNN=2)),
        Field(16, "axis", INT32, default=1),
        Field(17, "force_nd_im2col", BOOL, default=False),
    ]


class CropParameter(Message):
    FIELDS = [
        Field(1, "axis", INT32, default=2),
        Field(2, "offset", UINT32, repeated=True),
    ]


class DataParameter(Message):
    FIELDS = [
        Field(1, "source", STRING),
        Field(4, "batch_size", UINT32),
        Field(7, "rand_skip", UINT32, default=0),
        Field(8, "backend", ENUM, enum=DBBackend, default=0),
        Field(2, "scale", FLOAT, default=1.0),
        Field(3, "mean_file", STRING),
        Field(5, "crop_size", UINT32, default=0),
        Field(6, "mirror", BOOL, default=False),
        Field(9, "force_encoded_color", BOOL, default=False),
        Field(10, "prefetch", UINT32, default=4),
    ]


class DropoutParameter(Message):
    FIELDS = [Field(1, "dropout_ratio", FLOAT, default=0.5)]


class DummyDataParameter(Message):
    FIELDS = [
        Field(1, "data_filler", MESSAGE, message=FillerParameter,
              repeated=True),
        Field(6, "shape", MESSAGE, message=BlobShape, repeated=True),
        Field(2, "num", UINT32, repeated=True),
        Field(3, "channels", UINT32, repeated=True),
        Field(4, "height", UINT32, repeated=True),
        Field(5, "width", UINT32, repeated=True),
    ]


class EltwiseParameter(Message):
    FIELDS = [
        Field(1, "operation", ENUM, enum=EltwiseOp, default=EltwiseOp.SUM),
        Field(2, "coeff", FLOAT, repeated=True),
        Field(3, "stable_prod_grad", BOOL, default=True),
    ]


class ELUParameter(Message):
    FIELDS = [Field(1, "alpha", FLOAT, default=1.0)]


class EmbedParameter(Message):
    FIELDS = [
        Field(1, "num_output", UINT32),
        Field(2, "input_dim", UINT32),
        Field(3, "bias_term", BOOL, default=True),
        Field(4, "weight_filler", MESSAGE, message=FillerParameter),
        Field(5, "bias_filler", MESSAGE, message=FillerParameter),
    ]


class ExpParameter(Message):
    FIELDS = [
        Field(1, "base", FLOAT, default=-1.0),
        Field(2, "scale", FLOAT, default=1.0),
        Field(3, "shift", FLOAT, default=0.0),
    ]


class FlattenParameter(Message):
    FIELDS = [
        Field(1, "axis", INT32, default=1),
        Field(2, "end_axis", INT32, default=-1),
    ]


class HDF5DataParameter(Message):
    FIELDS = [
        Field(1, "source", STRING),
        Field(2, "batch_size", UINT32),
        Field(3, "shuffle", BOOL, default=False),
    ]


class HDF5OutputParameter(Message):
    FIELDS = [Field(1, "file_name", STRING)]


class HingeLossParameter(Message):
    FIELDS = [Field(1, "norm", ENUM, enum=Enum("Norm", L1=1, L2=2),
                    default=1)]


class ImageDataParameter(Message):
    FIELDS = [
        Field(1, "source", STRING),
        Field(4, "batch_size", UINT32, default=1),
        Field(7, "rand_skip", UINT32, default=0),
        Field(8, "shuffle", BOOL, default=False),
        Field(9, "new_height", UINT32, default=0),
        Field(10, "new_width", UINT32, default=0),
        Field(11, "is_color", BOOL, default=True),
        Field(2, "scale", FLOAT, default=1.0),
        Field(3, "mean_file", STRING),
        Field(5, "crop_size", UINT32, default=0),
        Field(6, "mirror", BOOL, default=False),
        Field(12, "root_folder", STRING),
    ]


class InfogainLossParameter(Message):
    FIELDS = [Field(1, "source", STRING), Field(2, "axis", INT32, default=1)]


class InnerProductParameter(Message):
    FIELDS = [
        Field(1, "num_output", UINT32),
        Field(2, "bias_term", BOOL, default=True),
        Field(3, "weight_filler", MESSAGE, message=FillerParameter),
        Field(4, "bias_filler", MESSAGE, message=FillerParameter),
        Field(5, "axis", INT32, default=1),
        Field(6, "transpose", BOOL, default=False),
    ]


class InputParameter(Message):
    FIELDS = [Field(1, "shape", MESSAGE, message=BlobShape, repeated=True)]


class LogParameter(Message):
    FIELDS = [
        Field(1, "base", FLOAT, default=-1.0),
        Field(2, "scale", FLOAT, default=1.0),
        Field(3, "shift", FLOAT, default=0.0),
    ]


class LRNParameter(Message):
    FIELDS = [
        Field(1, "local_size", UINT32, default=5),
        Field(2, "alpha", FLOAT, default=1.0),
        Field(3, "beta", FLOAT, default=0.75),
        Field(4, "norm_region", ENUM, enum=NormRegion, default=0),
        Field(5, "k", FLOAT, default=1.0),
    ]


class MemoryDataParameter(Message):
    # fields 1-4 are upstream; 100+ are CoS fork extensions
    # (ImageDataSource.scala:49-60, ImageDataFrame.scala:35-45,
    #  CaffeNet.cpp:183-188)
    FIELDS = [
        Field(1, "batch_size", UINT32),
        Field(2, "channels", UINT32),
        Field(3, "height", UINT32),
        Field(4, "width", UINT32),
        Field(100, "source", STRING),
        Field(101, "dataframe_format", STRING, default="parquet"),
        Field(102, "dataframe_column_select", STRING, repeated=True),
        Field(103, "image_encoded", BOOL, default=True),
        Field(104, "share_in_parallel", BOOL, default=False),
    ]


class ContrastiveLossParameter(Message):
    FIELDS = [
        Field(1, "margin", FLOAT, default=1.0),
        # legacy: penalize (margin - d^2) instead of (margin - d)^2
        Field(2, "legacy_version", BOOL, default=False),
    ]


class MVNParameter(Message):
    FIELDS = [
        Field(1, "normalize_variance", BOOL, default=True),
        Field(2, "across_channels", BOOL, default=False),
        Field(3, "eps", FLOAT, default=1e-9),
    ]


class ParameterParameter(Message):
    FIELDS = [Field(1, "shape", MESSAGE, message=BlobShape)]


class PoolingParameter(Message):
    FIELDS = [
        Field(1, "pool", ENUM, enum=PoolMethod, default=PoolMethod.MAX),
        Field(4, "pad", UINT32, default=0),
        Field(9, "pad_h", UINT32, default=0),
        Field(10, "pad_w", UINT32, default=0),
        Field(2, "kernel_size", UINT32),
        Field(5, "kernel_h", UINT32),
        Field(6, "kernel_w", UINT32),
        Field(3, "stride", UINT32, default=1),
        Field(7, "stride_h", UINT32),
        Field(8, "stride_w", UINT32),
        Field(12, "global_pooling", BOOL, default=False),
        Field(13, "round_mode", ENUM,
              enum=Enum("RoundMode", CEIL=0, FLOOR=1), default=0),
    ]


class PowerParameter(Message):
    FIELDS = [
        Field(1, "power", FLOAT, default=1.0),
        Field(2, "scale", FLOAT, default=1.0),
        Field(3, "shift", FLOAT, default=0.0),
    ]


class SPPParameter(Message):
    FIELDS = [
        Field(1, "pyramid_height", UINT32),
        Field(2, "pool", ENUM, enum=PoolMethod, default=PoolMethod.MAX),
    ]


class PReLUParameter(Message):
    FIELDS = [
        Field(1, "filler", MESSAGE, message=FillerParameter),
        Field(2, "channel_shared", BOOL, default=False),
    ]


class PythonParameter(Message):
    FIELDS = [
        Field(1, "module", STRING),
        Field(2, "layer", STRING),
        Field(3, "param_str", STRING),
        Field(4, "share_in_parallel", BOOL, default=False),
    ]


class RecurrentParameter(Message):
    FIELDS = [
        Field(1, "num_output", UINT32, default=0),
        Field(2, "weight_filler", MESSAGE, message=FillerParameter),
        Field(3, "bias_filler", MESSAGE, message=FillerParameter),
        Field(4, "debug_info", BOOL, default=False),
        Field(5, "expose_hidden", BOOL, default=False),
    ]


class ReductionParameter(Message):
    FIELDS = [
        Field(1, "operation", ENUM,
              enum=Enum("ReductionOp", SUM=1, ASUM=2, SUMSQ=3, MEAN=4),
              default=1),
        Field(2, "axis", INT32, default=0),
        Field(3, "coeff", FLOAT, default=1.0),
    ]


class ReLUParameter(Message):
    FIELDS = [Field(1, "negative_slope", FLOAT, default=0.0)]


class ReshapeParameter(Message):
    FIELDS = [
        Field(1, "shape", MESSAGE, message=BlobShape),
        Field(2, "axis", INT32, default=0),
        Field(3, "num_axes", INT32, default=-1),
    ]


class ScaleParameter(Message):
    FIELDS = [
        Field(1, "axis", INT32, default=1),
        Field(2, "num_axes", INT32, default=1),
        Field(3, "filler", MESSAGE, message=FillerParameter),
        Field(4, "bias_term", BOOL, default=False),
        Field(5, "bias_filler", MESSAGE, message=FillerParameter),
    ]


class BiasParameter(Message):
    FIELDS = [
        Field(1, "axis", INT32, default=1),
        Field(2, "num_axes", INT32, default=1),
        Field(3, "filler", MESSAGE, message=FillerParameter),
    ]


class BatchNormParameter(Message):
    FIELDS = [
        Field(1, "use_global_stats", BOOL),
        Field(2, "moving_average_fraction", FLOAT, default=0.999),
        Field(3, "eps", FLOAT, default=1e-5),
    ]


class SigmoidParameter(Message):
    FIELDS = []


class SliceParameter(Message):
    FIELDS = [
        Field(3, "axis", INT32, default=1),
        Field(2, "slice_point", UINT32, repeated=True),
        Field(1, "slice_dim", UINT32, default=1),
    ]


class SoftmaxParameter(Message):
    FIELDS = [Field(2, "axis", INT32, default=1)]


class TanHParameter(Message):
    FIELDS = []


class ThresholdParameter(Message):
    FIELDS = [Field(1, "threshold", FLOAT, default=0.0)]


class TileParameter(Message):
    FIELDS = [Field(1, "axis", INT32, default=1), Field(2, "tiles", INT32)]


# ---------------------------------------------------------------------------
# CoS fork: CoSData layer parameters (SURVEY §2.9, lrcn_cos.prototxt)
# ---------------------------------------------------------------------------

class TopBlob(Message):
    """One typed top of a CoSData layer (DataFrameSource.scala Top class)."""
    FIELDS = [
        Field(1, "name", STRING),
        Field(2, "type", ENUM, enum=TopBlobType, default=TopBlobType.FLOAT),
        Field(3, "channels", UINT32, default=1),
        Field(4, "height", UINT32, default=1),
        Field(5, "width", UINT32, default=1),
        Field(6, "out_channels", UINT32, default=0),
        Field(7, "out_height", UINT32, default=0),
        Field(8, "out_width", UINT32, default=0),
        Field(9, "sample_num_axes", INT32, default=3),
        Field(10, "transpose", BOOL, default=False),
        Field(11, "transform_param", MESSAGE,
              message=TransformationParameter),
    ]


class CoSDataParameter(Message):
    FIELDS = [
        Field(1, "batch_size", UINT32, default=1),
        Field(2, "source", STRING),
        Field(3, "dataframe_format", STRING, default="parquet"),
        Field(4, "top", MESSAGE, message=TopBlob, repeated=True),
    ]


class MoEParameter(Message):
    """Extension (no reference equivalent): top-k routed
    mixture-of-experts FFN; the expert dimension shards over the ep
    mesh axis.  Two dispatches share the message:

    * `dispatch: "capacity"` (default; what an old prototxt gets):
      softmax router, ReLU experts `W1`/`W2`, fixed expert capacity
      C = ceil(k N / E * capacity_factor), overflow dropped.  A second
      top, when declared, emits the load-balancing auxiliary loss
      (weight it via the layer's second loss_weight).
    * `dispatch: "dropless"`: assignments are sorted by expert and run
      through grouped matrix products over the experts this layer
      HOLDS; nothing is dropped whatever the imbalance.  `scoring`
      picks the router (`softmax` | `sigmoid`), `selection_bias` adds
      a bias blob that moves only the top-k choice (give it lr_mult 0),
      the chosen scores are normalised over the k and multiplied by
      `routed_scaling_factor`; `gated` experts are SiLU-gated
      (`W_gate`/`W_up`/`W_down`; `gate_activation: "relu"` gates them
      by a ReLU instead; `gated: false` experts are two
      matrices `W1`/`W2` around `activation`: "relu" or "relu2", the
      squared ReLU), `shared_hidden_dim` > 0 adds shared
      experts as one FFN every token passes, SiLU-gated for gated
      experts, else `S_up`/`S_down` around `activation`.  `experts_held` /
      `first_expert` tell the layer which experts live here (0 = all):
      it routes over all `num_experts` and computes the part of the
      sum that experts [first_expert, first_expert + experts_held)
      give, plus the shared experts.  A SECOND BOTTOM, when given, is
      what the router reads (the experts read the first): a block whose
      router sits before its attention hands it the block's normed
      input, and the router's gradient flows back into that.  Tops
      after the first: a (3,)
      vector [rows per held expert max/mean, share of the k N
      assignments on held experts, dropped assignments], then the
      rows per held expert."""
    FIELDS = [
        Field(1, "num_experts", UINT32, default=4),
        Field(2, "hidden_dim", UINT32, default=256),
        Field(3, "weight_filler", MESSAGE, message=FillerParameter),
        Field(4, "top_k", UINT32, default=1),
        Field(5, "capacity_factor", FLOAT, default=1.25),
        Field(6, "dispatch", STRING, default="capacity"),
        Field(7, "scoring", STRING, default="softmax"),
        Field(8, "selection_bias", BOOL, default=False),
        Field(9, "routed_scaling_factor", FLOAT, default=1.0),
        Field(10, "gated", BOOL, default=False),
        Field(11, "shared_hidden_dim", UINT32, default=0),
        Field(12, "experts_held", UINT32, default=0),
        Field(13, "first_expert", UINT32, default=0),
        # added to the sum of the k chosen scores before the division
        # (lfm2_moe: 1e-6); 0 = the bare sum
        Field(14, "norm_epsilon", FLOAT, default=0.0),
        # the shared experts' sum is multiplied by sigmoid(x w_sg), one
        # gate a token (qwen3_next); blob `S_sgate` (D, 1)
        Field(15, "shared_gate", BOOL, default=False),
        # what a `gated` expert's gate passes through: "silu"
        # (silu(x W_gate) * (x W_up)) or "relu" (smallthinker's ReGLU)
        Field(16, "gate_activation", STRING, default="silu"),
        # what an expert WITHOUT a gate (`gated: false`: `W1`/`W2`, and
        # the shared expert as `S_up`/`S_down`) passes through: "relu"
        # or "relu2" (relu(x)^2: nemotron_h's squared ReLU)
        Field(17, "activation", STRING, default="relu"),
    ]


class AttentionParameter(Message):
    """Extension (no reference equivalent): self-attention on
    time-major (T, B, D) input.

    `MultiHeadAttention`: one fused `W_qkv` of `num_heads` equal heads
    of `head_dim`, no positions.  `LatentAttention` (deepseek_v3's
    multi-head latent attention without q compression): `W_q` gives
    `num_heads` x (`qk_nope_head_dim` + `qk_rope_head_dim`); `W_kva`
    gives a `kv_lora_rank`-wide latent and ONE `qk_rope_head_dim`-wide
    rotary key shared by all heads; RMSNorm(latent) `W_kvb` gives
    `num_heads` x (`qk_nope_head_dim` keys + `v_head_dim` values);
    rotary positions (adjacent pairs, `rope_theta`) turn the rope
    parts; q/k are nope + rope wide, v is `v_head_dim` wide.
    `GroupedQueryAttention`: separate `W_q` (`num_heads` x `head_dim`),
    `W_k` / `W_v` (`num_kv_heads` x `head_dim`; 0 = `num_heads`) and
    `W_o`; query head h reads key/value head h // (num_heads /
    num_kv_heads); `qk_norm` puts an RMSNorm (`rms_norm_eps`, one
    `head_dim`-wide scale each, shared by the heads) on every q and k
    head; `rotary` turns adjacent pairs of the whole head (or, with
    `rotary_dim` > 0, of its first `rotary_dim` dims alone, angle
    t theta^(-2i/rotary_dim)) by `rope_theta` after the norms;
    `output_gate` widens `W_q` to `num_heads` x 2 `head_dim` (query
    and gate of a head side by side) and multiplies the attention's
    output by sigmoid(gate) before `W_o` (qwen3_next); `window` > 0
    (with `causal`, any of the three types' dispatch, set on
    `GroupedQueryAttention`) lets row t see the `window` keys up to and
    with its own (t - window < s <= t): a sliding-window layer; a layer
    without positions is `rotary: false`.  `differential` (on
    `GroupedQueryAttention`, arXiv:2410.05258) reads the `num_heads`
    query heads and the `num_kv_heads` key heads as PAIRS (2p, 2p + 1)
    and the value heads pairwise side by side, 2 x `head_dim` wide:
    o_p = softmax(q_2p k^T) v - lambda softmax(q_2p+1 k'^T) v with
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + `lambda_init` from four
    `head_dim`-wide blobs, then an RMSNorm over the pair's 2 x
    `head_dim` (one `sub_norm` scale, eps `rms_norm_eps`) times (1 -
    `lambda_init`).  `emit_kv`: two more tops, the layer's keys and
    values as the dispatch takes them; `shared_kv`: two more BOTTOMS,
    another layer's keys and values, and then `W_q` and `W_o` are the
    layer's only matrices.  All
    types share one attention dispatch (flash kernel on the TPU when
    the shape tiles, XLA einsums otherwise); GSPMD partitions the
    einsums over whatever mesh axes the activations carry."""
    FIELDS = [
        Field(1, "num_heads", UINT32, default=1),
        Field(2, "head_dim", UINT32, default=64),
        Field(3, "causal", BOOL, default=False),
        Field(4, "weight_filler", MESSAGE, message=FillerParameter),
        Field(5, "kv_lora_rank", UINT32, default=0),
        Field(6, "qk_nope_head_dim", UINT32, default=0),
        Field(7, "qk_rope_head_dim", UINT32, default=0),
        Field(8, "v_head_dim", UINT32, default=0),
        Field(9, "rope_theta", FLOAT, default=10000.0),
        Field(10, "rms_norm_eps", FLOAT, default=1e-6),
        Field(11, "num_kv_heads", UINT32, default=0),
        Field(12, "qk_norm", BOOL, default=False),
        Field(13, "rotary", BOOL, default=False),
        Field(14, "rotary_dim", UINT32, default=0),
        Field(15, "output_gate", BOOL, default=False),
        # with `causal`: row t sees the `window` keys t - window < s <= t
        # (its own among them) and no others; 0 = its whole past
        Field(16, "window", UINT32, default=0),
        Field(17, "differential", BOOL, default=False),
        Field(18, "lambda_init", FLOAT, default=0.8),
        Field(19, "lambda_filler", MESSAGE, message=FillerParameter),
        # tops 1, 2 = this layer's k (B, Hkv, T, head_dim) and v
        Field(20, "emit_kv", BOOL, default=False),
        # bottoms 1, 2 = another layer's k and v; no W_k, no W_v
        Field(21, "shared_kv", BOOL, default=False),
    ]


class ShortConvParameter(Message):
    """Extension: the gated short convolution (`ShortConv`, lfm2) on
    time-major (T, B, D) input: `[b, c, u] = split3(x W_in)`, a
    depthwise causal convolution of `taps` taps over time on `b * u`
    (zero before t = 0), `y = (c * conv) W_out`.  Blobs `W_in` (3D, D),
    `taps` (D, taps; tap j multiplies the input at t - (taps - 1) + j),
    `W_out` (D, D), then with `bias_term` the convolution's (D,) bias."""
    FIELDS = [
        Field(1, "taps", UINT32, default=3),
        Field(2, "bias_term", BOOL, default=False),
        Field(3, "weight_filler", MESSAGE, message=FillerParameter),
    ]


class GatedDeltaNetParameter(Message):
    """Extension: the Gated DeltaNet operator (`GatedDeltaNet`,
    qwen3_next's linear-attention layer) on time-major (T, B, D) input.
    `[q, k, v, z] = x W_qkvz` (`num_k_heads` x `head_k_dim` each for q
    and k, `num_v_heads` x `head_v_dim` each for v and z, in whole
    blocks in this order), `[b, a] = x W_ba` (`num_v_heads` each);
    a depthwise causal convolution of `conv_taps` taps and a SiLU over
    concat(q, k, v); per value head beta = sigmoid(b), g = -exp(A_log)
    softplus(a + dt_bias); q and k L2-normalised, q / sqrt(head_k_dim);
    key head h // (num_v_heads / num_k_heads) serves value head h; the
    gated delta rule S_t = e^g S_(t-1) + k (beta (v - (e^g S_(t-1))^T
    k))^T, o = S_t^T q over the sequence in chunks of `chunk` tokens;
    RMSNorm(o) (`rms_norm_eps`, one `head_v_dim`-wide scale) x silu(z);
    `W_out`.  Blobs `W_qkvz`, `W_ba`, `taps` (channels, conv_taps),
    `A_log` (log of uniform [1e-3, 16)), `dt_bias` (1), `norm` (1),
    `W_out`."""
    FIELDS = [
        Field(1, "num_k_heads", UINT32, default=1),
        Field(2, "num_v_heads", UINT32, default=1),
        Field(3, "head_k_dim", UINT32, default=128),
        Field(4, "head_v_dim", UINT32, default=128),
        Field(5, "conv_taps", UINT32, default=4),
        Field(6, "chunk", UINT32, default=64),
        Field(7, "rms_norm_eps", FLOAT, default=1e-6),
        Field(8, "weight_filler", MESSAGE, message=FillerParameter),
    ]


class RMSNormParameter(Message):
    """Extension: y = x / sqrt(mean(x^2 over the last axis) + eps) *
    scale, one `scale` blob the width of the last axis (constant 1
    unless `scale_filler` says otherwise)."""
    FIELDS = [
        Field(1, "eps", FLOAT, default=1e-6),
        Field(2, "scale_filler", MESSAGE, message=FillerParameter),
    ]


class LayerNormParameter(Message):
    """Extension: y = (x - mean) / sqrt(var + eps) * scale + bias over
    the last axis (biased variance), blobs `scale` (constant 1 unless
    `scale_filler` says otherwise) and `bias` (0 unless `bias_filler`),
    each the width of the last axis."""
    FIELDS = [
        Field(1, "eps", FLOAT, default=1e-5),
        Field(2, "scale_filler", MESSAGE, message=FillerParameter),
        Field(3, "bias_filler", MESSAGE, message=FillerParameter),
    ]


class MambaParameter(Message):
    """Extension: the selective state-space mixer (`Mamba`,
    arXiv:2312.00752) on time-major (T, B, D) input.  `[a, z] = x W_in`
    (`d_inner` each); u = silu(taps over time of a + conv_bias), a
    depthwise causal convolution of `d_conv` taps; `[r, B, C] = u W_x`
    (`dt_rank`, `d_state`, `d_state`); dt = softplus(r W_dt + dt_bias);
    A = -exp(A_log); per channel c and state n
    s_t = exp(dt_t A) s_(t-1) + dt_t u_t B_t, y_t = sum_n s_t C_t + D u_t
    over the sequence in chunks of `chunk` tokens, float32;
    out = (y * silu(z)) W_out.  A second top, where the layer has one,
    is y itself: the memory a `GatedMemoryUnit` further on reads.
    Blobs `W_in` (2 d_inner, D), `taps` (d_inner, d_conv), `conv_bias`
    (`conv_filler` fills both), `W_x`, `W_dt`, `dt_bias` (the inverse
    softplus of a log-uniform draw in [`dt_min`, `dt_max`]), `A_log`
    (log of 1..d_state in every channel), `D` (1), `W_out`."""
    FIELDS = [
        Field(1, "d_inner", UINT32, default=0),
        Field(2, "d_state", UINT32, default=16),
        Field(3, "d_conv", UINT32, default=4),
        Field(4, "dt_rank", UINT32, default=0),
        Field(5, "chunk", UINT32, default=64),
        Field(6, "dt_min", FLOAT, default=1e-3),
        Field(7, "dt_max", FLOAT, default=1e-1),
        Field(8, "weight_filler", MESSAGE, message=FillerParameter),
        Field(9, "conv_filler", MESSAGE, message=FillerParameter),
    ]


class Mamba2Parameter(Message):
    """Extension: the Mamba-2 mixer (`Mamba2`, the state-space duality
    form of arXiv:2405.21060) on time-major (T, B, D) input: `num_heads`
    heads of `head_dim` channels (d_inner = their product), `n_groups`
    groups that share B and C (head h reads group h // (num_heads /
    n_groups)), a (`head_dim`, `d_state`) matrix state a head under ONE
    scalar decay a head and token.  `[z | xBC | dt] = x W_in` (d_inner,
    d_inner + 2 n_groups d_state, num_heads); xBC = silu(taps over time
    of xBC + conv_bias), a depthwise causal convolution of `d_conv`
    taps; `[u | B | C] = xBC`; dt = softplus(dt + dt_bias); A =
    -exp(A_log); S_t = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T, y_t =
    S_t C_t + D u_t over the sequence in chunks of `chunk` tokens,
    float32; y = RMSNorm(y * silu(z)) over each of the `n_groups`
    groups of channels (eps `rms_norm_eps`, one d_inner-wide scale);
    out = y W_out.  Blobs `W_in` (d_inner + conv channels + num_heads,
    D), `taps` (conv channels, d_conv), `conv_bias` (`conv_filler`
    fills both), `dt_bias` (the inverse softplus of a log-uniform draw
    in [`dt_min`, `dt_max`]), `A_log` (log of 1..num_heads), `D` (1),
    `norm` (1), `W_out` (D, d_inner)."""
    FIELDS = [
        Field(1, "num_heads", UINT32, default=0),
        Field(2, "head_dim", UINT32, default=64),
        Field(3, "n_groups", UINT32, default=1),
        Field(4, "d_state", UINT32, default=128),
        Field(5, "d_conv", UINT32, default=4),
        Field(6, "chunk", UINT32, default=128),
        Field(7, "dt_min", FLOAT, default=1e-3),
        Field(8, "dt_max", FLOAT, default=1e-1),
        Field(9, "rms_norm_eps", FLOAT, default=1e-5),
        Field(10, "weight_filler", MESSAGE, message=FillerParameter),
        Field(11, "conv_filler", MESSAGE, message=FillerParameter),
    ]


class GatedMemoryUnitParameter(Message):
    """Extension: the Gated Memory Unit (`GatedMemoryUnit`,
    arXiv:2507.06607) on time-major input: bottoms x (T, B, D) and a
    memory m (T, B, M) that an earlier layer made;
    y = (m * silu(x W_in)) W_out.  Blobs `W_in` (M, D), `W_out` (D, M)."""
    FIELDS = [
        Field(1, "weight_filler", MESSAGE, message=FillerParameter),
    ]


# ---------------------------------------------------------------------------
# LayerParameter / NetParameter / SolverParameter
# ---------------------------------------------------------------------------

class LayerParameter(Message):
    FIELDS = [
        Field(1, "name", STRING),
        Field(2, "type", STRING),
        Field(3, "bottom", STRING, repeated=True),
        Field(4, "top", STRING, repeated=True),
        Field(10, "phase", ENUM, enum=Phase),
        Field(5, "loss_weight", FLOAT, repeated=True),
        Field(6, "param", MESSAGE, message=ParamSpec, repeated=True),
        Field(7, "blobs", MESSAGE, message=BlobProto, repeated=True),
        Field(11, "propagate_down", BOOL, repeated=True),
        Field(8, "include", MESSAGE, message=NetStateRule, repeated=True),
        Field(9, "exclude", MESSAGE, message=NetStateRule, repeated=True),
        # CoS fork extensions (numbers fork-private; text names are the API)
        Field(147, "source_class", STRING),
        Field(148, "cos_data_param", MESSAGE, message=CoSDataParameter),
        Field(149, "attention_param", MESSAGE, message=AttentionParameter),
        Field(150, "moe_param", MESSAGE, message=MoEParameter),
        Field(151, "rms_norm_param", MESSAGE, message=RMSNormParameter),
        Field(153, "short_conv_param", MESSAGE, message=ShortConvParameter),
        Field(154, "gated_delta_net_param", MESSAGE,
              message=GatedDeltaNetParameter),
        Field(155, "layer_norm_param", MESSAGE, message=LayerNormParameter),
        Field(156, "mamba_param", MESSAGE, message=MambaParameter),
        Field(157, "gated_memory_unit_param", MESSAGE,
              message=GatedMemoryUnitParameter),
        Field(158, "mamba2_param", MESSAGE, message=Mamba2Parameter),
        # consecutive layers that give the same non-empty name form one
        # block whose activations are recomputed in the backward pass
        # (Net.apply: one jax.checkpoint around the block); COS_REMAT
        # stays the per-layer override it is
        Field(152, "recompute_block", STRING),
        # layer-specific params (upstream numbers)
        Field(100, "transform_param", MESSAGE,
              message=TransformationParameter),
        Field(101, "loss_param", MESSAGE, message=LossParameter),
        Field(102, "accuracy_param", MESSAGE, message=AccuracyParameter),
        Field(103, "argmax_param", MESSAGE, message=ArgMaxParameter),
        Field(139, "batch_norm_param", MESSAGE, message=BatchNormParameter),
        Field(141, "bias_param", MESSAGE, message=BiasParameter),
        Field(104, "concat_param", MESSAGE, message=ConcatParameter),
        Field(105, "contrastive_loss_param", MESSAGE,
              message=ContrastiveLossParameter),
        Field(132, "spp_param", MESSAGE, message=SPPParameter),
        Field(106, "convolution_param", MESSAGE,
              message=ConvolutionParameter),
        Field(144, "crop_param", MESSAGE, message=CropParameter),
        Field(107, "data_param", MESSAGE, message=DataParameter),
        Field(108, "dropout_param", MESSAGE, message=DropoutParameter),
        Field(109, "dummy_data_param", MESSAGE, message=DummyDataParameter),
        Field(110, "eltwise_param", MESSAGE, message=EltwiseParameter),
        Field(140, "elu_param", MESSAGE, message=ELUParameter),
        Field(137, "embed_param", MESSAGE, message=EmbedParameter),
        Field(111, "exp_param", MESSAGE, message=ExpParameter),
        Field(135, "flatten_param", MESSAGE, message=FlattenParameter),
        Field(112, "hdf5_data_param", MESSAGE, message=HDF5DataParameter),
        Field(113, "hdf5_output_param", MESSAGE,
              message=HDF5OutputParameter),
        Field(114, "hinge_loss_param", MESSAGE, message=HingeLossParameter),
        Field(115, "image_data_param", MESSAGE, message=ImageDataParameter),
        Field(116, "infogain_loss_param", MESSAGE,
              message=InfogainLossParameter),
        Field(117, "inner_product_param", MESSAGE,
              message=InnerProductParameter),
        Field(143, "input_param", MESSAGE, message=InputParameter),
        Field(134, "log_param", MESSAGE, message=LogParameter),
        Field(118, "lrn_param", MESSAGE, message=LRNParameter),
        Field(119, "memory_data_param", MESSAGE,
              message=MemoryDataParameter),
        Field(120, "mvn_param", MESSAGE, message=MVNParameter),
        Field(145, "parameter_param", MESSAGE, message=ParameterParameter),
        Field(121, "pooling_param", MESSAGE, message=PoolingParameter),
        Field(122, "power_param", MESSAGE, message=PowerParameter),
        Field(131, "prelu_param", MESSAGE, message=PReLUParameter),
        Field(130, "python_param", MESSAGE, message=PythonParameter),
        Field(146, "recurrent_param", MESSAGE, message=RecurrentParameter),
        Field(136, "reduction_param", MESSAGE, message=ReductionParameter),
        Field(123, "relu_param", MESSAGE, message=ReLUParameter),
        Field(133, "reshape_param", MESSAGE, message=ReshapeParameter),
        Field(142, "scale_param", MESSAGE, message=ScaleParameter),
        Field(124, "sigmoid_param", MESSAGE, message=SigmoidParameter),
        Field(126, "slice_param", MESSAGE, message=SliceParameter),
        Field(125, "softmax_param", MESSAGE, message=SoftmaxParameter),
        Field(127, "tanh_param", MESSAGE, message=TanHParameter),
        Field(128, "threshold_param", MESSAGE, message=ThresholdParameter),
        Field(138, "tile_param", MESSAGE, message=TileParameter),
    ]


# ---------------------------------------------------------------------------
# V1 legacy layers (deprecated upstream format still used by many published
# .caffemodel files, e.g. the original bvlc_reference_caffenet.caffemodel)
# ---------------------------------------------------------------------------

# V1LayerParameter.LayerType enum value → modern string type
V1_LAYER_TYPES = {
    35: "AbsVal", 1: "Accuracy", 30: "ArgMax", 2: "BNLL", 3: "Concat",
    37: "ContrastiveLoss", 4: "Convolution", 5: "Data", 39: "Deconvolution",
    6: "Dropout", 32: "DummyData", 7: "EuclideanLoss", 25: "Eltwise",
    38: "Exp", 8: "Flatten", 9: "HDF5Data", 10: "HDF5Output", 28: "HingeLoss",
    11: "Im2col", 12: "ImageData", 13: "InfogainLoss", 14: "InnerProduct",
    15: "LRN", 29: "MemoryData", 16: "MultinomialLogisticLoss", 34: "MVN",
    17: "Pooling", 26: "Power", 18: "ReLU", 19: "Sigmoid",
    27: "SigmoidCrossEntropyLoss", 36: "Silence", 20: "Softmax",
    21: "SoftmaxWithLoss", 22: "Split", 33: "Slice", 23: "TanH",
    24: "WindowData", 31: "Threshold",
}


class V1LayerParameter(Message):
    """Just enough of the deprecated layer message to import weights:
    name/type/blobs (+ topology for completeness)."""
    FIELDS = [
        Field(2, "bottom", STRING, repeated=True),
        Field(3, "top", STRING, repeated=True),
        Field(4, "name", STRING),
        Field(5, "type", ENUM,
              enum=Enum("V1LayerType", NONE=0, **{f"T{k}": k
                                                  for k in V1_LAYER_TYPES})),
        Field(6, "blobs", MESSAGE, message=BlobProto, repeated=True),
        Field(7, "blobs_lr", FLOAT, repeated=True),
        Field(8, "weight_decay", FLOAT, repeated=True),
    ]

    def type_name(self) -> str:
        return V1_LAYER_TYPES.get(int(self.type), f"V1:{int(self.type)}")


class NetParameter(Message):
    FIELDS = [
        Field(1, "name", STRING),
        Field(3, "input", STRING, repeated=True),
        Field(8, "input_shape", MESSAGE, message=BlobShape, repeated=True),
        Field(4, "input_dim", INT32, repeated=True),
        Field(5, "force_backward", BOOL, default=False),
        Field(6, "state", MESSAGE, message=NetState),
        Field(7, "debug_info", BOOL, default=False),
        Field(100, "layer", MESSAGE, message=LayerParameter, repeated=True),
        Field(2, "layers", MESSAGE, message=V1LayerParameter,
              repeated=True),
    ]


class SolverParameter(Message):
    FIELDS = [
        Field(24, "net", STRING),
        Field(25, "net_param", MESSAGE, message=NetParameter),
        Field(1, "train_net", STRING),
        Field(2, "test_net", STRING, repeated=True),
        Field(21, "train_net_param", MESSAGE, message=NetParameter),
        Field(22, "test_net_param", MESSAGE, message=NetParameter,
              repeated=True),
        Field(26, "train_state", MESSAGE, message=NetState),
        Field(27, "test_state", MESSAGE, message=NetState, repeated=True),
        Field(3, "test_iter", INT32, repeated=True),
        Field(4, "test_interval", INT32, default=0),
        Field(19, "test_compute_loss", BOOL, default=False),
        Field(32, "test_initialization", BOOL, default=True),
        Field(5, "base_lr", FLOAT),
        Field(6, "display", INT32),
        Field(33, "average_loss", INT32, default=1),
        Field(7, "max_iter", INT32),
        Field(36, "iter_size", INT32, default=1),
        Field(8, "lr_policy", STRING),
        Field(9, "gamma", FLOAT),
        Field(10, "power", FLOAT),
        Field(11, "momentum", FLOAT),
        Field(12, "weight_decay", FLOAT),
        Field(29, "regularization_type", STRING, default="L2"),
        Field(13, "stepsize", INT32),
        Field(34, "stepvalue", INT32, repeated=True),
        Field(35, "clip_gradients", FLOAT, default=-1.0),
        Field(14, "snapshot", INT32, default=0),
        Field(15, "snapshot_prefix", STRING),
        Field(16, "snapshot_diff", BOOL, default=False),
        Field(37, "snapshot_format", ENUM, enum=SnapshotFormat,
              default=SnapshotFormat.BINARYPROTO),
        Field(17, "solver_mode", ENUM, enum=SolverMode,
              default=SolverMode.GPU),
        Field(18, "device_id", INT32, default=0),
        Field(20, "random_seed", INT64, default=-1),
        Field(40, "type", STRING, default="SGD"),
        Field(31, "delta", FLOAT, default=1e-8),
        Field(39, "momentum2", FLOAT, default=0.999),
        Field(38, "rms_decay", FLOAT, default=0.99),
        Field(23, "debug_info", BOOL, default=False),
        Field(28, "snapshot_after_train", BOOL, default=True),
        Field(30, "solver_type", ENUM, enum=SolverType,
              default=SolverType.SGD),
    ]


class SolverState(Message):
    """Serialized optimizer state (.solverstate): iter + momentum history."""
    FIELDS = [
        Field(1, "iter", INT32),
        Field(2, "learned_net", STRING),
        Field(3, "history", MESSAGE, message=BlobProto, repeated=True),
        Field(4, "current_step", INT32, default=0),
    ]
