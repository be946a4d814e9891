"""setup_s (s): process start to the opening of the window: imports,
weights made on the device, the fleet built, the warm-up request served
(compiling, or loading from the persistent cache, every program the
window runs), and the traffic laid out."""


def read(run):
    return run.setup_s
