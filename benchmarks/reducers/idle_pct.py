"""1 - union of the device-operation intervals over the traced window."""


def reduce(reduced: dict, spec: dict):
    if not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
