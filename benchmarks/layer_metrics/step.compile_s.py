"""Wall time of lowered.compile(): a compile on a cold cache, a read of the
persistent cache on a warm one (hits of requests are on an earlier line)."""


def read(run):
    return run["setup"]["phases"]["compile_s"]
