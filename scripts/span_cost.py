#!/usr/bin/env python
"""What the loop's spans cost: microseconds per step of the seven spans
``train_loop`` opens (``data_load`` over assemble / to_device / stage,
``step`` over compute / loss_sync), with a recorder and without.

    python scripts/span_cost.py [--jax] [--checkout DIR]

``--jax`` imports jax first (spans then mirror into ``TraceAnnotation``, as
in a training process); ``--checkout`` measures another tree's spans (the
parent commit, for a before/after). Each sample is one window's worth of
steps into a fresh recorder, as a benchmark run records them; the median
over the samples is reported. A host number. Budget (``PERF.md`` §7): under
50 µs per step with the recorder on.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

STEPS_PER_WINDOW = 150
WINDOWS = 300


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--jax", action="store_true")
    parser.add_argument("--checkout", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    args = parser.parse_args()
    sys.path.insert(0, args.checkout)
    if args.jax:
        import jax  # noqa: F401
    from network_distributed_pytorch_tpu.observe import spans
    from network_distributed_pytorch_tpu.observe.sinks import MemorySink
    from network_distributed_pytorch_tpu.observe.telemetry import Telemetry

    span = spans.span

    def window(telemetry) -> float:
        t0 = time.perf_counter()
        with spans.recording(telemetry):
            for i in range(STEPS_PER_WINDOW):
                with span("data_load", step=i):
                    with span("data_load/assemble"):
                        pass
                    with span("data_load/to_device"):
                        pass
                    with span("data_load/stage"):
                        pass
                with span("step", step=i):
                    with span("step/compute", step=i):
                        pass
                    with span("step/loss_sync", step=i):
                        pass
        return (time.perf_counter() - t0) / STEPS_PER_WINDOW * 1e6

    out = {"checkout": args.checkout, "jax_imported": args.jax, "spans_per_step": 7}
    for label, recorder in (("no_recorder", lambda: None), ("recorder", lambda: Telemetry([MemorySink()]))):
        window(recorder())  # warm up
        per_step = statistics.median(window(recorder()) for _ in range(WINDOWS))
        out[label] = {"us_per_step": round(per_step, 2), "us_per_span": round(per_step / 7, 2)}
    sys.stdout.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
