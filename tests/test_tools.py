"""Tool tests — ToolTest.scala analog: converter row counts and the COCO
caption → vocab → embedding → caption round trip (:86-137)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from caffeonspark_tpu.data import (LmdbReader, LmdbWriter,
                                   SequenceFileReader)
from caffeonspark_tpu.data.synthetic import make_images
from caffeonspark_tpu.proto.caffe import Datum
from caffeonspark_tpu.tools import (Vocab, binary2dataframe,
                                    binary2sequence,
                                    embedding_to_caption,
                                    image_caption_to_embedding,
                                    lmdb2dataframe, lmdb2sequence,
                                    sequence2lmdb)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CAPTIONS = [
    "a dog runs across the green park",
    "a cat sits on the red mat",
    "the dog and the cat play in the park",
    "a bird flies over the park",
]


@pytest.fixture()
def image_dir(tmp_path):
    import cv2
    d = tmp_path / "imgs"
    d.mkdir()
    imgs, labels = make_images(6, channels=3, height=16, width=16, seed=2)
    lines = []
    for i in range(6):
        img = (imgs[i].transpose(1, 2, 0) * 255).astype(np.uint8)
        name = f"img{i}.jpg"
        cv2.imwrite(str(d / name), img)
        lines.append(f"{name} {int(labels[i])}")
    (tmp_path / "labels.txt").write_text("\n".join(lines))
    return d, tmp_path / "labels.txt"


def test_binary2sequence_and_back(image_dir, tmp_path):
    d, labels = image_dir
    seq = str(tmp_path / "imgs.seq")
    n = binary2sequence(str(d), seq, str(labels))
    assert n == 6
    recs = list(SequenceFileReader(seq))
    assert len(recs) == 6
    datum = Datum.from_binary(recs[0][1])
    assert datum.encoded
    assert datum.label >= 0
    # sequence → LMDB → dataframe chain
    lmdb_dir = str(tmp_path / "lmdb")
    assert sequence2lmdb(seq, lmdb_dir) == 6
    with LmdbReader(lmdb_dir) as r:
        assert r.entries == 6
    pq_path = str(tmp_path / "df.parquet")
    assert lmdb2dataframe(lmdb_dir, pq_path) == 6
    import pyarrow.parquet as pq
    t = pq.read_table(pq_path)
    assert t.num_rows == 6
    assert set(t.column_names) >= {"id", "label", "data", "encoded"}


def test_binary2dataframe(image_dir, tmp_path):
    d, labels = image_dir
    out = str(tmp_path / "b2d.parquet")
    assert binary2dataframe(str(d), out, str(labels)) == 6
    import pyarrow.parquet as pq
    t = pq.read_table(out)
    assert t.num_rows == 6


def test_lmdb2sequence(tmp_path):
    recs = [(b"%04d" % i, Datum(channels=1, height=2, width=2,
                                data=bytes(4), label=i).to_binary())
            for i in range(10)]
    LmdbWriter(str(tmp_path / "l")).write(recs)
    seq = str(tmp_path / "out.seq")
    assert lmdb2sequence(str(tmp_path / "l"), seq) == 10
    back = list(SequenceFileReader(seq))
    assert [k for k, _ in back] == ["%04d" % i for i in range(10)]


def test_vocab_build_save_load(tmp_path):
    v = Vocab.build(CAPTIONS, vocab_size=12)
    assert v.word_to_id("the") == 2          # most frequent first
    assert v.word_to_id("zzz_unknown") == 1  # UNK
    v.save(str(tmp_path / "vocab"))
    v2 = Vocab.load(str(tmp_path / "vocab"))
    assert v2.words == v.words
    assert v2.word_to_id("park") == v.word_to_id("park")


def test_caption_embedding_round_trip(tmp_path):
    """ToolTest.scala:86-137 analog: caption → embedding → caption."""
    rows = [{"id": str(i), "caption": c, "data": b""}
            for i, c in enumerate(CAPTIONS)]
    vocab = Vocab.build(CAPTIONS, vocab_size=100)
    emb = image_caption_to_embedding(rows, vocab, caption_length=10)
    e0 = emb[0]
    assert len(e0["input_sentence"]) == 11
    assert e0["input_sentence"][0] == 0          # start marker
    assert e0["cont_sentence"][0] == 0 and e0["cont_sentence"][1] == 1
    assert e0["target_sentence"][-1] == 0 or 0 in e0["target_sentence"]
    back = embedding_to_caption(emb, vocab)
    for orig, rec in zip(CAPTIONS, back):
        assert rec["caption"] == " ".join(
            w.lower() for w in orig.split())


def test_simulator_cli():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.simulator",
         "-synthetic", "8", "-batch", "4", "-iterations", "3",
         "-height", "64", "-width", "64"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-600:]
    assert "images/sec" in r.stdout
    # the uint8 split's host half reports its wire size (1 B/px)
    if "devxf" in r.stdout:
        assert "uint8" in r.stdout


def test_display_utils(tmp_path):
    from caffeonspark_tpu.tools.display_utils import (
        show_captions, show_features_histogram, show_image_grid)
    from caffeonspark_tpu.data.synthetic import make_images
    import cv2
    imgs, labels = make_images(5, channels=3, height=16, width=16,
                               seed=1)
    out = show_image_grid([imgs[i] for i in range(5)],
                          labels=[str(l) for l in labels[:5]],
                          output=str(tmp_path / "grid.png"))
    assert os.path.getsize(out) > 1000
    ok, buf = cv2.imencode(".jpg",
                           (imgs[0].transpose(1, 2, 0) * 255)
                           .astype(np.uint8))
    rows = [{"data": bytes(buf), "caption": "a test image"}]
    out2 = show_captions(rows, output=str(tmp_path / "cap.png"))
    assert os.path.getsize(out2) > 1000
    out3 = show_features_histogram(
        [{"f": [0.1, 0.5]}, {"f": [0.9]}], "f",
        output=str(tmp_path / "hist.png"))
    assert os.path.getsize(out3) > 1000


def test_coco_pipeline_cli(tmp_path, image_dir):
    d, _ = image_dir
    coco = {
        "images": [{"id": i, "file_name": f"img{i}.jpg",
                    "height": 16, "width": 16} for i in range(4)],
        "annotations": [{"image_id": i, "caption": CAPTIONS[i]}
                        for i in range(4)],
    }
    cf = tmp_path / "captions.json"
    cf.write_text(json.dumps(coco))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.converters",
         "cocodataset", "-captionFile", str(cf), "-imageRoot", str(d),
         "-imageCaptionDFDir", str(tmp_path / "capdf"),
         "-vocabDir", str(tmp_path / "vocab"),
         "-embeddingDFDir", str(tmp_path / "embdf"),
         "-vocabSize", "50", "-captionLength", "8"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    assert "cocodataset: 4 records" in r.stdout
    import pyarrow.parquet as pq
    t = pq.read_table(str(tmp_path / "embdf" / "embedding.parquet"))
    assert t.num_rows == 4
    assert set(t.column_names) >= {"id", "data", "input_sentence",
                                   "target_sentence", "cont_sentence"}

    # re-run: the existing vocab must be REUSED, not rebuilt
    # (CocoDataSetConverter.scala:35-39 fs.exists branch)
    vocab_file = tmp_path / "vocab" / "vocab.txt"
    before = vocab_file.read_text()
    vocab_file.write_text(before + "zzz_sentinel\n")
    r2 = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.converters",
         "cocodataset", "-captionFile", str(cf), "-imageRoot", str(d),
         "-vocabDir", str(tmp_path / "vocab"),
         "-embeddingDFDir", str(tmp_path / "embdf2"),
         "-vocabSize", "50", "-captionLength", "8"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r2.returncode == 0, r2.stderr[-800:]
    assert "zzz_sentinel" in vocab_file.read_text()

    # caption-less json → image-only embedding (Image2Embedding path),
    # json output format
    cf2 = tmp_path / "images_only.json"
    cf2.write_text(json.dumps({"images": coco["images"]}))
    r3 = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.converters",
         "cocodataset", "-captionFile", str(cf2), "-imageRoot", str(d),
         "-vocabDir", str(tmp_path / "vocab"),
         "-embeddingDFDir", str(tmp_path / "embdf3"),
         "-outputFormat", "json"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r3.returncode == 0, r3.stderr[-800:]
    lines = (tmp_path / "embdf3" / "embedding.json").read_text() \
        .strip().splitlines()
    assert len(lines) == 4
    row = json.loads(lines[0])
    assert row["label"] == 0.0 and "input_sentence" not in row
    import base64
    assert len(base64.b64decode(row["data"])) > 100  # real jpeg bytes
