"""setup_s (s): process start to the start of the window: data, store,
server, warm-up and every compile or cache load. Host clock."""


def read(run):
    return run.setup_s
