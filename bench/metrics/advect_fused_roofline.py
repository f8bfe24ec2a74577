"""advect_fused_roofline: least HBM time of the window's work over the
Mosaic kernels' device time (HBM-bound only: no vector peak is published
for the v5e)."""
from bench.metrics._shares import roofline_share as read  # noqa: F401
