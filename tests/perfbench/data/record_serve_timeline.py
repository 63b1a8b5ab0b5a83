"""How ``serve_timeline_v5e.xplane.pb.gz`` was made (my chip run, PR 34):

  chiprun --chips 1 -- python3 tests/perfbench/data/record_serve_timeline.py

The recording of ``record_serve_phases.py`` again, made with the engine
of PR 34: the same short window of ``mistral7b-serve-steady`` through
the serve runner's own ``run`` (2 s of its traffic, the last second
traced), cut by that script's ``cut_xplane`` to ``STEPS`` engine steps
around the stretch's first prefill chunk.  What is new is in the
program: ``bf.engine.device_wait`` and ``bf.engine.host_copy`` inside
``bf.engine.token_fetch``, ``launch=`` on the spans that dispatch a
program and ``start=`` on the chunk's.  The host plane keeps the
runtime's own events (``DoEnqueueProgram``, ``tpu::System::Execute=>
Done``), which ``perfbench/harness/step_timeline.py`` pairs with the
executions by order.  The cut goes to ``chiprun_out/`` with a
description of its planes beside it; the tests read the copy kept here.
"""

import gzip
import importlib.util
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT]

NAME = "serve_timeline_v5e"
SEED = 2147650101
SECONDS = 2.0
STEPS = 8


def older_script():
    spec = importlib.util.spec_from_file_location(
        "record_serve_phases", os.path.join(HERE, "record_serve_phases.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main() -> int:
    import jax
    from jax.profiler import ProfileData

    from perfbench.harness import clocks, device, loader, trace as tr
    from perfbench.runners import serve

    device.configure_compile_cache()
    cell = loader.load_cell("mistral7b-serve-steady")
    out = os.path.join(ROOT, "chiprun_out")
    log = os.path.join(out, NAME + "_trace")
    shutil.rmtree(log, ignore_errors=True)
    os.makedirs(log)
    result = serve.run(cell, SEED, SECONDS, True, jax.devices()[:1],
                       clocks.Spans(), clocks.now(), log)
    with open(tr.find_xplane(log), "rb") as fh:
        raw = fh.read()
    print(len(raw), "bytes; correct:", result["correct"])
    shutil.rmtree(log)
    cut = older_script().cut_xplane(raw, STEPS)
    path = os.path.join(out, NAME + ".xplane.pb")
    with open(path, "wb") as fh:
        fh.write(cut)
    with open(os.path.join(out, NAME + ".txt"), "w") as fh:
        fh.write(tr.describe(ProfileData.from_file(path), 12))
    os.remove(path)
    with gzip.open(path + ".gz", "wb", 9) as fh:
        fh.write(cut)
    print(len(cut), "bytes cut,", os.path.getsize(path + ".gz"), "gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
