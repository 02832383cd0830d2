"""Record the small trace that tests/test_spans.py reads, and list what a
capture of a live trainer holds.  Run on the chip:

    python3 perfbench/tools/record_cos_trace.py <out dir>

A small `-train` job (3x32x32 raw records, one convolution, batch 64,
held to ~25 steps a second) runs through `CaffeOnSpark.train` with the
whole pipelined runtime.  Two captures are taken of it:

  1. through the program's `step_observer`, python tracer off, about a
     second: device ops and the program's own `cos.*` spans, small enough
     to keep as <out dir>/cos_small.xplane.pb;
  2. through the trainer's own `POST /v1/profile` (COS_METRICS_PORT), as
     an operator would: not kept, its `cos.*` spans are listed thread by
     thread, with the device's busy time beside them.
"""

import json
import os
import shutil
import sys
import tempfile
import threading
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NET = '''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{lmdb}" batch_size: 64
    channels: 3 height: 32 width: 32 }}
  transform_param {{ crop_size: 28 mirror: true mean_value: 120 }} }}
layer {{ name: "conv" type: "Convolution" bottom: "data" top: "conv"
  convolution_param {{ num_output: 32 kernel_size: 5
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu" type: "ReLU" bottom: "conv" top: "conv" }}
layer {{ name: "ip" type: "InnerProduct" bottom: "conv" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}'''


def listing(path):
    """cos.* spans of one capture, thread by thread."""
    from perfbench.harness import spans as S
    from perfbench.harness import trace as tr
    S.print_by_thread(S.load(path))
    red = tr.reduce(tr.load(path))
    for plane, d in red["devices"].items():
        lo, hi = d["window"]
        print(f"  {plane}: busy {d['busy_s']:.4f} s of {hi - lo:.4f} s, "
              f"{d['modules']} programs run")


def main(out):
    os.environ["COS_METRICS_PORT"] = "0"
    os.environ["COS_FAULT_STEP_DELAY_MS"] = "40"
    import jax
    import numpy as np
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data import LmdbWriter, get_source
    from caffeonspark_tpu.processor import CaffeProcessor
    from caffeonspark_tpu.proto.caffe import Datum
    from perfbench.harness.trace import find_xplane

    work = tempfile.mkdtemp(prefix="cos_trace_")
    rng = np.random.default_rng(24)
    LmdbWriter(os.path.join(work, "lmdb")).write([
        (b"%06d" % i, Datum(channels=3, height=32, width=32,
                            data=rng.integers(0, 255, 3 * 32 * 32,
                                              dtype=np.uint8).tobytes(),
                            label=i % 10).to_binary())
        for i in range(512)])
    net = os.path.join(work, "net.prototxt")
    with open(net, "w") as f:
        f.write(NET.format(lmdb=os.path.join(work, "lmdb")))
    solver = os.path.join(work, "solver.prototxt")
    with open(solver, "w") as f:
        f.write(f'net: "{net}"\nbase_lr: 0.01\nlr_policy: "fixed"\n'
                'max_iter: 100000000\nsnapshot_prefix: "x"\n'
                'snapshot_after_train: false\nrandom_seed: 24\n')
    conf = Config(["-conf", solver, "-train", "-output", work])
    proc = CaffeProcessor.instance(conf)
    small = os.path.join(work, "small")
    first_done = threading.Event()

    def observer(it, n, batch, params, st, out_):
        if it == 20:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(small, profiler_options=opts)
        elif it == 45:
            jax.block_until_ready(out_["loss"])
            jax.profiler.stop_trace()
            first_done.set()

    proc.step_observer = observer
    job = threading.Thread(
        target=lambda: CaffeOnSpark().train(get_source(
            conf.train_data_layer(), phase_train=True), conf),
        daemon=True)
    job.start()
    try:
        if not first_done.wait(600):
            raise RuntimeError("the job never reached step 45")
        req = urllib.request.Request(
            f"http://127.0.0.1:{proc._obs_server.port}/v1/profile",
            data=json.dumps({"duration_ms": 1500}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            live = json.loads(r.read())
    finally:
        proc.stop()
        job.join(60)
    os.makedirs(out, exist_ok=True)
    kept = os.path.join(out, "cos_small.xplane.pb")
    shutil.copy(find_xplane(small), kept)
    print(f"device {jax.devices()[0].device_kind}; recorded "
          f"{os.path.getsize(kept)} bytes -> {kept}")
    listing(kept)
    print(f"POST /v1/profile on the live trainer: {live}")
    listing(find_xplane(live["trace_dir"]))
    shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
