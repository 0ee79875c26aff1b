"""100 x (1 - busy/window) of the traced window, from the device trace."""


def read(p: dict, run) -> float | None:
    if run.trace is None or not run.busy_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.traced_s)
