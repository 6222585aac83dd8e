"""Device time of the Pallas kernels of block-sparse top-k attention
(_sparse_fwd_kernel, _bwd_dkv_sparse_kernel, _bwd_dq_sparse_kernel: the causal
kernels' tiles masked from a bitmap of the blocks each row and K/V group
chose) over device busy time, device 0. Nothing to read in a step that runs
none of them."""
from benchmarks.lib import trace as tracing
from benchmarks.lib.flops_minicpm_sala import SPARSE_KERNELS
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(
        run, lambda event: tracing.kernel_of(event) in SPARSE_KERNELS)
