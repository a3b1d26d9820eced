"""Process start to the window's opening: device, weights from the seed,
compile-cache load, shape warm-up and warm-up traffic."""

def read(run):
    return run.setup_s
