"""Milliseconds per step in which a collective runs on a chip and no
other operation does, on the chip where that is longest."""


def read(run):
    exposed = run.trace["collective_exposed_s"]
    if not exposed or run.trace["collective_s"] == [0.0] * len(exposed) \
            or run.steps == 0:
        return None
    return 1e3 * max(exposed) / run.steps
