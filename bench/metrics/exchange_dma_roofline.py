"""exchange_dma_roofline: least interconnect time of the window's halo
exchange over the remote-DMA kernels' device time, summed over chips."""
from bench.metrics._exchange import exchange_dma_roofline as read  # noqa: F401
