"""Device-idle milliseconds per batch of the traced window; what the host
was doing in the gaps is the result line's ``breakdown.idle_gaps``."""


def reduce(reduced: dict, spec: dict):
    if not reduced["batches"]:
        return None
    return 1e3 * (reduced["window_s"] - reduced["busy_s"]) / reduced["batches"]
