"""How ``tiny_train_v5e.xplane.pb`` was made (my chip run, PR 23):

  chiprun --chips 1 -- python3 tests/perfbench/data/record_tiny_trace.py

A few steps of the tiny decoder of ``tests/perfbench/conftest.py`` (bf16,
flash kernel, 2 x 256 tokens) through the train runner's own ``Job`` and
``traced_stretch`` on one TPU v5e; the trace goes to
``chiprun_out/tiny_train_v5e.xplane.pb`` with a description of its
planes beside it.  The tests read the copy kept here.
"""

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT, os.path.dirname(HERE)]


def main() -> int:
    import tempfile

    import jax
    from jax.profiler import ProfileData

    import conftest as C
    from perfbench.harness import clocks, loader, trace as tr
    from perfbench.runners import train

    root = os.path.join(tempfile.mkdtemp(), "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"))
    config = dict(C.TINY_DECODER)
    config["cuts"] = dict(config["cuts"], train={
        "compute_dtype": "bfloat16", "param_dtype": "float32",
        "attn_impl": "flash", "remat": False})
    config["head_dim"], config["hidden_size"] = 128, 512
    traffic = dict(C.TINY_TRAFFIC["tiny-train"], seq_len=256)
    C.add_cell(root, "tiny", config, "tiny-flash", traffic)
    cell = loader.load_cell("tiny", root)
    job = train.Job(cell, 1, jax.devices()[:1], clocks.Spans())
    for _ in range(3):
        jax.block_until_ready(job.call())
    out = os.path.join(ROOT, "chiprun_out")
    log = os.path.join(out, "tiny_trace")
    shutil.rmtree(log, ignore_errors=True)
    os.makedirs(log)
    train.traced_stretch(job, 0.02, log)
    path = tr.find_xplane(log)
    shutil.copy(path, os.path.join(out, "tiny_train_v5e.xplane.pb"))
    with open(os.path.join(out, "tiny_train_v5e.txt"), "w") as fh:
        fh.write(tr.describe(ProfileData.from_file(path), 12))
    print(os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
