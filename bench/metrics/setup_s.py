"""Set-up time: process start to the first timed query (weights, build,
compile or cache load, queries, pool fill, warm-up), host clock."""


def read(run):
    return run.setup_s
