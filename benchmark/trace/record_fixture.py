"""How ``fixtures/tiny_step.xplane.pb.gz`` was made: three steps of the tiny
DistilBERT preset (the rehearsal sizes, but bf16 and the kernels "auto"
selects on the chip) through ``train_loop`` on one v5e chip, with the
compiled step's HLO text beside it. Run on the chip:

    chiprun -- python3 -m benchmark.trace.record_fixture
"""

import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 3


def main() -> int:
    import jax

    from network_distributed_pytorch_tpu import hostenv
    from network_distributed_pytorch_tpu.experiments.common import train_loop
    from network_distributed_pytorch_tpu.parallel import make_mesh
    from network_distributed_pytorch_tpu.utils.hlo_audit import hlo_text_of_compiled

    from .. import cells, compose
    from ..run import shapes_of
    from . import reduce

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_fixture: no TPU")
    hostenv.configure_compile_cache()
    cell = cells.cell("imdb_psgd16_b16")
    cfg = compose.resolved(cell["config"], cell["workload"], rehearsal=True)
    cfg["compute_dtype"] = "bfloat16"
    mesh = make_mesh()
    built = cells.module("builders", cell["config"]["builder"]).build(cfg, 0, mesh)
    state = built.state
    batches = built.batches(0)
    state_shapes = shapes_of(state)
    for _ in range(3):
        batch = next(batches)
        state, loss = built.step(state, batch)
    jax.device_get(loss)
    hlo = hlo_text_of_compiled(built.step.fn.lower(state_shapes, shapes_of(batch)).compile())
    out = os.path.join(cells.CHECKOUT, "chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    train_loop(built.step, state, built.batches, epochs=1, prefetch=2,
               on_step_end=lambda epoch, done, st: done >= STEPS)
    jax.profiler.stop_trace()
    found = reduce.reduce_dir(out, hlo)
    print(found.report())
    import glob

    pb = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    with open(pb, "rb") as src, gzip.open(os.path.join(out, "tiny_step.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(out, "tiny_step.hlo.txt.gz"), "wt") as f:
        f.write(hlo)
    print("record_fixture:", os.path.getsize(os.path.join(out, "tiny_step.xplane.pb.gz")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
