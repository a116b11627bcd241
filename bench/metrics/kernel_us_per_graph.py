"""Device time of the configuration's kernels in the profiler trace, per
graph their units answered, in chip-us."""


def read(run):
    ks = (run.dev or {}).get("kernels", {}).values()
    secs = sum(k["seconds"] for k in ks)
    graphs = sum(k["graphs"] for k in ks)
    return secs * 1e6 / graphs if secs > 0 and graphs else None
