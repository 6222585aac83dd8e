"""Command start -> first line of the worker's loop: ray_tpu.init, the
placement group, the TPU worker's spawn and the chip hand-over."""


def read(run):
    return run["setup"]["t_loop"] - run["t_command"]
