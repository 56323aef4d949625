"""Pallas TPU kernels for the compute hot-spots (validated on CPU in
interpret mode; see each module's docstring for the TPU blocking design).

The tiling/strategy choice is autotuned per (backend, shape-bucket) — see
:mod:`repro.kernels.autotune` and DESIGN.md §14."""
from .autotune import KernelConfig, autotune, get_config
from .ops import csr_aggregate, flash_decode, fused_gcn_layer, streamed_pairs
from .ref import csr_aggregate_ref, flash_decode_ref

__all__ = ["csr_aggregate", "flash_decode", "fused_gcn_layer",
           "csr_aggregate_ref", "flash_decode_ref",
           "KernelConfig", "autotune", "get_config", "streamed_pairs"]
