"""Device time of the lightning indexer in the full latent layers (/mla/:
the index queries', key's and weights' projections, the key's LayerNorm and
the rotation under ``indexer``; the indexer kernel, which scores every (row,
key) pair, finds each row's threshold and packs the words, and the pads
around it under ``select``; forward alone: nothing of it is differentiated and
the replay is handed the words) over device busy time, device 0: what choosing
costs beside attending. Nothing to read where no latent layer selects."""
from benchmarks.lib.kernel_readers import share_of_busy


def read(run):
    return share_of_busy(
        run, lambda event: "/mla/" in event.path and (
            "/indexer/" in event.path or "/select/" in event.path))
