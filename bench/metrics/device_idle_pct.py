"""Share of the traced window (the measured window, or its first
``trace_seconds`` where the configuration sets them) in which no
operation ran on the device, in %, averaged over the chips used."""


def read(run):
    if not run.dev or run.dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.dev["busy_s"] / run.dev["window_s"])
