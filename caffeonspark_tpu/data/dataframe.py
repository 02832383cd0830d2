"""DataFrameSource: generic multi-column Parquet → CoSData typed tops.

Reference: `caffe-grid/.../DataFrameSource.scala` (Top class :315-353,
nextBatch packing :225-302): each `cos_data_param.top {}` names a column
with a type in {STRING, INT, FLOAT, INT_ARRAY, FLOAT_ARRAY, RAW_IMAGE,
ENCODED_IMAGE, ENCODED_IMAGE_WITH_DIM}, per-top transform params, and
`transpose: true` producing time-major (T, B) layouts for recurrent nets
(`cos_data_layer.cpp:35-41`).
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, Sequence

import numpy as np

from ..proto.caffe import TopBlobType as T
from .source import DataSource, decode_image
from .transformer import Transformer


class DataFrameSource(DataSource):

    def __init__(self, layer, **kw):
        super().__init__(layer, **kw)
        self.tops = list(layer.cos_data_param.top)
        self.top_transformers = {}
        for top in self.tops:
            if top.has("transform_param"):
                self.top_transformers[top.name] = Transformer(
                    top.transform_param, phase_train=self.phase_train,
                    seed=self.seed + self.rank,
                    mean_dir=os.path.dirname(self.source_uri()) or None)

    def image_dims(self):
        for top in self.tops:
            if top.type in (T.RAW_IMAGE, T.ENCODED_IMAGE,
                            T.ENCODED_IMAGE_WITH_DIM):
                return (int(top.channels), int(top.height), int(top.width))
        return (0, 0, 0)

    # -- rows --------------------------------------------------------------
    def rows(self) -> Iterator[Dict]:
        fmt = self.layer.cos_data_param.dataframe_format or "parquet"
        path = self.source_uri()
        if fmt == "parquet":
            import pyarrow.parquet as pq
            table = pq.read_table(path)
        elif fmt == "json":
            import pyarrow.json as pj
            table = pj.read_json(path)
        else:
            raise ValueError(f"dataframe_format {fmt!r}")
        n = table.num_rows
        lo = self.rank * n // self.num_ranks
        hi = (self.rank + 1) * n // self.num_ranks
        d = table.slice(lo, hi - lo).to_pydict()
        names = table.column_names
        for i in range(hi - lo):
            yield {c: d[c][i] for c in names}

    def records(self):
        # SPI compat: yield rows (typed packing happens in next_batch)
        return self.rows()

    # -- packing -----------------------------------------------------------
    def _pack_top(self, top, values: Sequence) -> np.ndarray:
        b = len(values)
        t = top.type
        if t == T.INT or t == T.FLOAT:
            arr = np.asarray([float(v if v is not None else 0)
                              for v in values], np.float32)
            return arr.reshape(b, 1, 1, 1)
        if t in (T.INT_ARRAY, T.FLOAT_ARRAY):
            width = int(top.channels)
            out = np.zeros((b, width), np.float32)
            for i, v in enumerate(values):
                v = list(v or [])[:width]
                out[i, :len(v)] = v
            if top.transpose:
                return np.ascontiguousarray(out.T)   # (T, B) time-major
            return out
        if t == T.STRING:
            return np.asarray([str(v) for v in values], object)
        # image types
        c, h, w = int(top.channels), int(top.height), int(top.width)
        oh = int(top.out_height or h)
        ow = int(top.out_width or w)
        imgs = np.zeros((b, c, oh, ow), np.float32)
        for i, v in enumerate(values):
            payload = bytes(v) if isinstance(v, (bytes, bytearray)) \
                else bytes(v or [])
            if t == T.RAW_IMAGE:
                imgs[i] = np.frombuffer(payload, np.uint8).astype(
                    np.float32).reshape(c, h, w)[:, :oh, :ow]
            else:  # ENCODED_IMAGE / ENCODED_IMAGE_WITH_DIM
                imgs[i] = decode_image(payload, channels=c,
                                       resize_hw=(oh, ow))
        tr = self.top_transformers.get(top.name)
        if tr is not None:
            imgs = tr(imgs)
        return imgs

    def next_batch(self, rows: Sequence[Dict]) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {}
        if self.metrics is not None:
            # typed tops have no one-pass kernel: every batch is packed
            # the general way (PipelineMetrics counter, as images)
            self.metrics.incr("pack_general")
        for top in self.tops:
            col = top.name
            vals = [r.get(col) for r in rows]
            out[col] = self._pack_top(top, vals)
        return out

    # batches() comes from the DataSource base: records() returns rows()
    # here, so the shared shuffle/epoch logic applies unchanged.
