"""Per step, the time a collective operation ran on a chip while no compute
operation did, on the worst chip (profiler trace)."""


def read(inputs):
    red = inputs.get("trace")
    steps = inputs["counters"].get("steps_traced")
    if not red or not red.get("n_devices") or not steps:
        return None
    return red["collective_exposed_s"] / steps * 1e3
