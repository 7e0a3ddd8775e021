"""Host time per step to put the next batch on the chips: the step's
``data_load/to_device`` spans (the copy to device 0) and ``data_load/stage``
spans (the ``device_put`` that on four chips reshards it); median over the
steps outside the profiler's slice. Absent where those spans carry no step
(a program whose child spans do not take their parent's)."""


def read(run):
    per_step = {}
    for name in ("data_load/stage", "data_load/to_device"):
        for record in run.clean_spans(name):
            per_step[record["step"]] = per_step.get(record["step"], 0.0) + record["dur_s"]
    values = sorted(per_step.values())
    return 1e3 * values[len(values) // 2] if values else None
