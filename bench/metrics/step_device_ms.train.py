"""Device milliseconds of the jitted training step program per step,
the mean over the cell's chips (XLA module events named after the
program's ``step_fn``)."""


def read(run):
    per_dev = run.trace["module_s"].get("step_fn")
    if not per_dev or run.steps == 0:
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / run.steps
