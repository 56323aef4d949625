"""The aggregation kernels' least time over their device time in a GAT
cell: ``agg_kernel_roofline_pct``'s reading (the Mosaic custom calls of the
window against the least work of the model module), where the model
module is GAT's, whose least work counts every head's arc sum, its
transposed pass and its attention row dot (``bench/models/gat.py``). None
where no kernel ran."""
from bench.metrics.agg_kernel_roofline_pct import read  # noqa: F401
