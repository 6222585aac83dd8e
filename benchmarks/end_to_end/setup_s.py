"""From the command's start to the end of the warm-up step: init, worker
spawn, chip hand-over, parameter init, reference check, compile or cache
read, warm-up. One wall clock (time.time) read in both processes."""


def read(run):
    return run["setup"]["t_ready"] - run["t_command"]
