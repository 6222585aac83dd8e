"""Device time of operations under the sparse mixer's scope ``select``
(/sparse/select/: the compressed keys, every head's scores against them and
their soft-max, the sum over a group's heads, the max-pool to blocks, the
forced blocks, the ranking and the bitmap; forward alone: nothing of it is
differentiated and the replay is handed the set) over device busy time,
device 0: what choosing costs beside attending. Nothing to read where no
layer selects."""
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(
        run, lambda event: "/sparse/" in event.path and "/select/" in event.path)
