"""exchange_dma_ms: the remote-DMA exchange kernels' device time in the
traced window, summed per chip, averaged over the chips, per block. A
block exchanges, then computes, so this is its exposed exchange time."""
from bench.metrics._exchange import exchange_dma_ms as read  # noqa: F401
